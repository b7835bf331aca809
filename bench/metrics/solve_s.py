"""solve_s: the whole measured window over the jobs completed in it (s)."""


def read(facts: dict):
    return facts["window_s"] / facts["jobs"]
