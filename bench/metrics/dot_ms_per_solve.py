"""dot_ms_per_solve: device time of matrix products outside the operator
and k-means (Lanczos reorthogonalisation and Ritz products) per job (ms);
read only where the trace covers every job of the window."""


def read(facts: dict):
    trace = facts["trace"]
    if not trace or trace["coverage"] < 1.0:
        return None
    return 1e3 * trace["layer_s"]["dot"] / facts["jobs"]
