"""Records the engine put out over the rung slots it dispatched inside
the window, in percent. Read only while the rung did not change in the
window (the engine counts batches, not slots); otherwise nothing."""


def read(cell, run):
    a, b = run["evidence"]["books"]["start"], run["evidence"]["books"]["end"]
    if a["rung"] != b["rung"] or a["rung_changes"] != b["rung_changes"]:
        return None
    batches = (b["stages"].get("inference", (0, 0.0))[0]
               - a["stages"].get("inference", (0, 0.0))[0])
    if batches <= 0:
        return None
    return 100.0 * (b["records_out"] - a["records_out"]) / (batches * b["rung"])
