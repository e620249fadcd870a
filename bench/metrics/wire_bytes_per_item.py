"""Wire bytes received over items recovered (both sides), summed over the
window's reconciliations: the paper's communication cost."""


def read(w):
    reports = w.reports()
    items = sum(r.only_remote.shape[0] + r.only_local.shape[0]
                for r in reports)
    if not items:
        return None
    return sum(r.bytes_received for r in reports) / items
