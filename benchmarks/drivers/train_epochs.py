"""Traffic kind ``train_epochs``: ``Estimator.fit`` called epoch after
epoch over one seeded host dataset until the window's seconds are spent.

Set-up builds ONE estimator, drives it through its first ``check_steps``
optimizer steps (one ``fit`` of one batch each, rows that all differ),
keeps what the comparison needs from them on the host, runs one warm-up
epoch, and hands the same estimator to the window. After the window the
program's state is freed and the plain reference follows the same steps.
"""

import numpy as np

from benchmarks.harness import compare, program, tracing
from benchmarks.harness.window import now


def _leaf_norms(tree) -> dict:
    """{leaf path: l2 norm} in float64 on the host."""
    import jax
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(p, "key", getattr(p, "name", p)))
                        for p in path)
        a = np.asarray(leaf, np.float64)
        out[name] = float(np.sqrt(np.sum(a * a)))
    return out


def _to_host(tree):
    import jax
    return jax.tree_util.tree_map(lambda a: np.asarray(a), tree)


class Prepared:
    """What set-up hands to the window and to the comparison."""


def setup(cell, seed: int) -> Prepared:
    import analytics_zoo_tpu as zoo

    t = cell.traffic
    model_lib = cell.module("models")
    ref_lib = cell.module("references")
    batch, check_steps = int(t["batch_size"]), int(t["check_steps"])
    zoo.init_orca_context(cluster_mode="local")

    rng = np.random.default_rng(seed)
    p = Prepared()
    p.batch = batch
    p.x, p.y = model_lib.make_inputs(cell.config, t, rng,
                                     batch * int(t["steps_per_epoch"]))
    p.check_x, p.check_y = model_lib.make_inputs(cell.config, t, rng,
                                                 batch * check_steps)
    params = ref_lib.make_params(cell.config, seed)
    p.est = program.build_estimator(
        model_lib.build_module(cell.config), model_lib.LOSS,
        t["optimizer"], params, p.x[:2])
    del params

    # the first steps, through the window's own call and feed
    p.losses, p.first_moment, p.params_after = [], None, []
    for s in range(check_steps):
        rows = slice(s * batch, (s + 1) * batch)
        hist = p.est.fit((p.check_x[rows], p.check_y[rows]), epochs=1,
                         batch_size=batch)
        p.losses.append(float(hist["loss"][-1]))
        if s == 0:
            p.first_moment = _to_host(program.first_moment(p.est))
        p.params_after.append(_to_host(program.parameters(p.est)))
    return p


def warm_up(p: Prepared):
    p.est.fit((p.x, p.y), epochs=1, batch_size=p.batch)


def window(cell, p: Prepared, seconds: float, trace: bool, listener) -> dict:
    """Epoch after epoch until ``seconds`` are spent; the epoch that is
    running when they are is finished and counted, with its time. A traced
    run then goes on for ``trace_seconds`` more under the profiler: the
    rate is never taken with the profiler on."""
    import jax
    from analytics_zoo_tpu.common import telemetry

    steps_per_epoch = int(cell.traffic["steps_per_epoch"])
    listener.mark()
    before = telemetry.snapshot()
    epochs, failed = 0, 0
    t0 = now()
    while True:
        with tracing.span("fit_epoch"):
            hist = p.est.fit((p.x, p.y), epochs=1, batch_size=p.batch)
        epochs += 1
        if not np.isfinite(hist["loss"][-1]):
            failed += steps_per_epoch
        if now() - t0 >= seconds:
            break
    jax.block_until_ready(program.parameters(p.est))
    elapsed = now() - t0
    after = telemetry.snapshot()
    compiles = listener.since_mark()

    profiler, traced_epochs = None, 0
    if trace:
        profiler = tracing.ProfilerWindow(cell.chips)
        trace_s = float(cell.traffic.get("trace_seconds", 3.0))
        profiler.start()
        t1 = now()
        while now() - t1 < trace_s:
            with tracing.span("fit_epoch"):
                p.est.fit((p.x, p.y), epochs=1, batch_size=p.batch)
            traced_epochs += 1
        jax.block_until_ready(program.parameters(p.est))
        profiler.stop()
    steps = epochs * steps_per_epoch
    return {
        "attempted": steps, "failed": failed, "elapsed_s": elapsed,
        "samples": steps * p.batch,
        "rate": steps * p.batch / elapsed,
        "compiles": compiles,
        "telemetry": {"start": before, "end": after},
        "profiler": profiler,
        "traced_units": traced_epochs * steps_per_epoch * p.batch,
    }


def reference_readings(cell, seed: int, p: Prepared, quant=None,
                       fault: str = None) -> dict:
    """The plain reference over the same first steps: losses, leaf norms of
    the first gradient and of the parameters' change. ``quant`` computes it
    in the control's precision; ``fault`` plants one of the training faults
    (``half_batch``: the second half of every batch left out, the mean
    taken over the rest)."""
    import jax
    ref_lib = cell.module("references")
    adam = cell.module("references", "adam")
    t = cell.traffic
    batch, check_steps = p.batch, int(t["check_steps"])
    used = batch // 2 if fault == "half_batch" else batch
    block = min(int(t["reference_block_rows"]), used)
    loss_and_grad = ref_lib.make_loss_and_grad(
        cell.config, batch, block, quant=quant, used=used)
    params = ref_lib.make_params(cell.config, seed)
    start = params
    state = adam.init(params)
    losses, grad_norms, first_grad, changes = [], None, None, []
    for s in range(check_steps):
        order = program.epoch_order(batch, epoch=s)
        rows = np.arange(s * batch, (s + 1) * batch)[order]
        loss, grads = loss_and_grad(params, p.check_x[rows], p.check_y[rows],
                                    program.step_key(s))
        losses.append(float(loss))
        if s == 0:
            grad_norms, first_grad = _leaf_norms(grads), grads
        params, state = adam.step(params, grads, state,
                                  **t.get("optimizer_args", {}))
        del grads
        changes.append(_leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a - b, params, start)))
    return {"losses": losses, "grad": grad_norms, "grad_tree": first_grad,
            "changes": changes}


def program_readings(cell, seed: int, p: Prepared) -> dict:
    """The same three kinds of numbers, from what set-up kept of the
    program's own first steps."""
    import jax
    ref_lib = cell.module("references")
    start = _to_host(ref_lib.make_params(cell.config, seed))
    changes = [
        _leaf_norms(jax.tree_util.tree_map(lambda a, b: a - b, after, start))
        for after in p.params_after]
    b1 = float(cell.traffic.get("optimizer_args", {}).get("b1", 0.9))
    grad = jax.tree_util.tree_map(lambda m: m / np.float32(1.0 - b1),
                                  p.first_moment)
    return {"losses": p.losses, "grad": _leaf_norms(grad), "grad_tree": grad,
            "changes": changes}


def gaps(got: dict, want: dict) -> dict:
    """The numbers a cell may compare (its limits file names those it
    does): each step's loss; the first gradient as a gap of norms by the
    worst and the median leaf, and as a relative difference, whole and by
    the median leaf; the parameters' change after each step as a gap of
    norms by the worst leaf (``dparam1``, ``dparam2``, ... and ``dparam``
    after the last) and after the last by the median leaf. Leaves whose
    reference gradient is under a thousandth of the median leaf's move
    under Adam by round-off alone and are left out of the change."""
    import statistics
    out = {}
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        out[f"loss{i + 1}"] = compare.relative_gap(a, b)
    out["grad1"], out["grad1_leaf"] = compare.worst_leaf_gap(
        got["grad"], want["grad"])
    out["grad1_median"] = compare.median_leaf_gap(got["grad"], want["grad"])
    out["grad1_diff"], out["grad1_diff_median"] = \
        compare.relative_difference(got["grad_tree"], want["grad_tree"])
    floor = 1e-3 * statistics.median(want["grad"].values())
    still = [k for k, v in want["grad"].items() if v < floor]
    last = len(want["changes"])
    for k, (a, b) in enumerate(zip(got["changes"], want["changes"]), 1):
        name = "dparam" if k == last else f"dparam{k}"
        out[name], out[f"{name}_leaf"] = compare.worst_leaf_gap(
            a, b, skip=still)
    out["dparam_median"] = compare.median_leaf_gap(
        got["changes"][-1], want["changes"][-1], skip=still)
    out["skipped_leaves"] = still
    return out


def _program_and_reference(cell, seed: int, p: Prepared) -> tuple:
    """The program's readings, then — its state freed — the reference's."""
    got = program_readings(cell, seed, p)
    program.release(p.est)
    p.est = p.params_after = None
    return got, reference_readings(cell, seed, p)


def verify(cell, seed: int, p: Prepared) -> tuple:
    """Frees the program's state, runs the reference, compares."""
    got, want = _program_and_reference(cell, seed, p)
    g = gaps(got, want)
    checks = compare.Checks(cell.limits)
    for name in cell.limits:
        checks.add(name, g[name])
    notes = [f"not compared: " + ", ".join(
                 f"{k} {v!r}" for k, v in g.items()
                 if isinstance(v, float) and k not in cell.limits),
             "worst leaves: " + ", ".join(
                 f"{k[:-5]} {v}" for k, v in g.items()
                 if k.endswith("_leaf"))
             + f"; left out of dparam*: {g['skipped_leaves']}",
             f"losses program {got['losses']} reference {want['losses']}"]
    return checks, notes


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        listener) -> dict:
    from benchmarks.harness import device
    t_a = now()
    p = setup(cell, seed)
    t_b = now()
    warm_up(p)
    setup_s = now() - t_start
    w = window(cell, p, seconds, trace, listener)
    peak = device.memory_peak_bytes(cell.chips)
    t_c = now()
    checks, notes = verify(cell, seed, p)
    notes.append(f"phases s: imports {t_a - t_start:.1f}, build and first "
                 f"steps {t_b - t_a:.1f}, warm-up epoch "
                 f"{setup_s - (t_b - t_start):.1f}, reference and "
                 f"comparison {now() - t_c:.1f}")
    name = cell.traffic["rate_metric"]
    notes.append(f"window: {w['attempted']} steps, {w['samples']} samples "
                 f"in {w['elapsed_s']:.4f} s; lowerings/compiles inside "
                 f"{w['compiles']['count']}")
    return {"attempted": w["attempted"], "failed": w["failed"],
            "end_to_end": {name: w["rate"], "setup_s": setup_s},
            "checks": checks, "notes": notes, "memory_peak_bytes": peak,
            "evidence": w, "mode": "train"}


def control_readings(cell, seed: int, seconds: float, with_control: bool,
                     listener) -> dict:
    """For ``benchmarks/control.py``: the program's gaps from the
    reference on this seed, and (``with_control``) the gaps of the
    reference computed in the control's precision and of the reference
    with each training fault planted. No measured window: training's
    readings need none."""
    ref_lib = cell.module("references")
    p = setup(cell, seed)
    got, want = _program_and_reference(cell, seed, p)
    out = {"program": gaps(got, want)}
    if with_control:
        for name in cell.traffic["control"].split(","):
            out[f"control_{name}"] = gaps(reference_readings(
                cell, seed, p, quant=getattr(ref_lib, name)), want)
        out["fault_half_batch"] = gaps(reference_readings(
            cell, seed, p, fault="half_batch"), want)
    return out
