"""The engine's host stages per record: summed ``StageTimer`` time of the
named stages inside the window over the records the engine put out in
it, in ms."""


def read(cell, run, stages):
    books = run["evidence"]["books"]
    records = books["end"]["records_out"] - books["start"]["records_out"]
    if records <= 0:
        return None
    total = 0.0
    for stage in stages:
        total += (books["end"]["stages"].get(stage, (0, 0.0))[1]
                  - books["start"]["stages"].get(stage, (0, 0.0))[1])
    return 1e3 * total / records
