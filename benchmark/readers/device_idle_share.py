"""1 - the union of the device's operation intervals over the traced
window, in %; on several chips the mean over chips."""


def read(readings, params):
    if readings.trace is None:
        return None
    return 100.0 * (1.0 - readings.trace["busy_s"]
                    / readings.trace["window_s"])
