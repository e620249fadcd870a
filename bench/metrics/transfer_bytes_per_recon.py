"""Host→device bytes staged plus device→host bytes fetched by the device
decodes, per reconciliation over the window (the reports'
``transfer_bytes``).  A program whose reports lack the counter reads
nothing."""


def read(w):
    reports = w.reports()
    moved = [getattr(r, "transfer_bytes", None) for r in reports]
    if not reports or None in moved:
        return None
    return sum(moved) / len(reports)
