"""matvecs_per_solve: operator applications per job, a block of columns
counting once: the build's degree pass and the solver's own count from its
result (``num_matvecs``)."""


def read(facts: dict):
    return facts["matvecs"] / facts["jobs"]
