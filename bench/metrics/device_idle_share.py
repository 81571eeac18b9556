"""Share of the traced time with a request in flight in which no
operation ran on the device (1 - device busy / time in flight)."""


def read(rec):
    tr = rec["trace"]
    if not tr["inflight_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["inflight_s"])
