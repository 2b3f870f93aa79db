"""The whole step's share of the chips' peak: operations the mathematics
needs per sample or record (``benchmarks/flops/<family>.sample_flops``)
times the traced run's rate over the whole window, over chips times peak
(``benchmarks/peaks.json``), in percent."""


def read(cell, run):
    if not run.get("peaks"):
        return None
    flops = cell.module("flops").sample_flops(
        cell.config, cell.traffic, run["mode"])
    rate = run["evidence"]["rate"]
    return 100.0 * flops * rate / (cell.chips * run["peaks"]["flops_per_s"])
