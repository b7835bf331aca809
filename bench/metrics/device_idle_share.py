"""device_idle_share: the traced window's share in which no operation ran
on the device (%), averaged over the chips used."""


def read(facts: dict):
    trace = facts["trace"]
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
