"""The reader of the share of device decodes whose residual stayed on the
device (``resident_share``)."""
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import cell  # noqa: E402


def _window(reports):
    return SimpleNamespace(reports=lambda: reports)


def test_resident_share_reader():
    c = cell.load_cell("statesync-fleet8-d1000")
    assert "resident_share.fleet" in {m["name"] for m in c.per_layer}
    reader = c.reader({"name": "resident_share.fleet"})
    reports = [SimpleNamespace(device_decodes=6, resident_decodes=5),
               SimpleNamespace(device_decodes=2, resident_decodes=1)]
    assert reader.read(_window(reports)) == 75.0
    # a program without the counter reads nothing and raises nothing
    assert reader.read(_window([SimpleNamespace(device_decodes=3)])) is None
    assert reader.read(_window([])) is None
    assert reader.read(_window([SimpleNamespace(
        device_decodes=0, resident_decodes=0)])) is None
