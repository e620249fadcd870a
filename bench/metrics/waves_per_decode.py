"""Device peel waves per device decode over the window: the reports'
``device_waves`` summed, over their ``device_decodes`` summed.  A program
whose reports lack the counter reads nothing."""


def read(w):
    reports = w.reports()
    waves = [getattr(r, "device_waves", None) for r in reports]
    decodes = sum(r.device_decodes for r in reports)
    if None in waves or not decodes:
        return None
    return sum(waves) / decodes
