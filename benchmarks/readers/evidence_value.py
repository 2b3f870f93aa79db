"""A number the driver measured itself around its own calls."""


def read(cell, run, key: str):
    return run["evidence"].get(key)
