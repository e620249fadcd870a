"""Device decodes that found the unit's residual still on the device from
its last decode, and staged only the rows absorbed since (the reports'
``resident_decodes``), in % of the device decodes over the window.  A
program whose reports lack the counter reads nothing."""


def read(w):
    reports = w.reports()
    kept = [getattr(r, "resident_decodes", None) for r in reports]
    decodes = sum(r.device_decodes for r in reports)
    if None in kept or not decodes:
        return None
    return 100.0 * sum(kept) / decodes
