"""window_roofline: least time of the window step's work at the chip's
published peaks (bench/work.py) over its measured device time (%), summed
over the applications that end inside the traced window."""


def read(facts: dict):
    trace = facts["trace"]
    apps = trace["applications"] if trace else []
    if not apps or len(apps) > len(facts["columns"]):
        return None
    measured = sum(app["window"] for app in apps)
    least = sum(facts["window_least_s"][c]
                for c, _ in zip(facts["columns"], apps))
    return 100.0 * least / measured if measured > 0 else None
