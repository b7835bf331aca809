"""setup_s: process start to window start (s), compilation included."""


def read(facts: dict):
    return facts["setup_s"]
