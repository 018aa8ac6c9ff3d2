"""Share of the traced slice of a steady window, in %, in which no
operation ran on the device (1 - busy union / slice)."""


def read(records):
    trace = records.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
