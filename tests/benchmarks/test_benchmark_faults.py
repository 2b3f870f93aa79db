"""The timed path broken underneath: ``correct`` has to come out false.

One test for each fault a cell can have: a step that returns its state
unchanged; a step that leaves one leaf of the parameters unmoved; half of
the batch left out, the mean taken over the rest; an answer altered where
it is produced. (No cell exchanges anything between
chips yet.) And the control and the planted faults of the reference read
far above the reference itself, at the tiny size."""

import numpy as np
import pytest

from benchmarks.harness import tiny


def _run(capsys, root, cell):
    rc, line, _ = tiny.run_cell(capsys, root, cell, 77, 0.5)
    return rc, line


def _break_train_step(monkeypatch, fault):
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.learn.estimator import JaxEstimator
    build = JaxEstimator._build_train_step

    def broken_build(self):
        fresh = self._train_step is None
        build(self)
        if not fresh:
            return
        step = self._train_step

        def unchanged(state, x, y):
            keep = jax.tree_util.tree_map(jnp.copy, state)
            _, logs = step(state, x, y)
            return keep, logs

        def half_batch(state, x, y):
            half = x.shape[0] // 2
            x = jnp.concatenate([x[:half], x[:half]])
            y = jnp.concatenate([y[:half], y[:half]])
            return step(state, x, y)

        def leaf_frozen(state, x, y):
            kept = jnp.copy(state["params"]["bert"]["block_1"]["ffn_norm"]
                            ["bias"])
            new, logs = step(state, x, y)
            new["params"]["bert"]["block_1"]["ffn_norm"]["bias"] = kept
            return new, logs

        self._train_step = {"unchanged": unchanged, "half_batch": half_batch,
                            "leaf_frozen": leaf_frozen}[fault]

    monkeypatch.setattr(JaxEstimator, "_build_train_step", broken_build)


@pytest.mark.parametrize("cell", tiny.all_cells("train_epochs"))
@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "leaf_frozen"])
def test_broken_train_step_is_not_correct(cell, fault, tmp_path, capsys,
                                          monkeypatch):
    _break_train_step(monkeypatch, fault)
    rc, line = _run(capsys, tiny.make_root(tmp_path), cell)
    assert rc == 0 and line["correct"] is False, line
    failed = [k for k, v in line["compared"].items()
              if not v["value"] <= v["limit"]]
    assert failed
    if fault == "unchanged":
        # a state left unchanged reads 1 by the worst-leaf measure
        assert line["compared"]["dparam_median"]["value"] \
            == pytest.approx(1.0)
        assert line["compared"]["grad1"]["value"] == pytest.approx(1.0)
        assert line["compared"]["dparam1"]["value"] == pytest.approx(1.0)
    elif fault == "leaf_frozen":
        # one leaf of 40 unmoved: only the worst leaf's change sees it
        assert failed == ["dparam1"]
        assert line["compared"]["dparam1"]["value"] \
            == pytest.approx(1.0, abs=1e-3)
    else:
        # the fault the gaps of the first gradient are there to catch
        assert "grad1" in failed and "grad1_diff" in failed


@pytest.mark.parametrize("cell", tiny.all_cells("serve"))
def test_altered_answer_is_not_correct(cell, tmp_path, capsys, monkeypatch):
    from analytics_zoo_tpu.serving import engine
    init = engine.ClusterServing.__init__
    seen = {"n": 0}

    def alter(pred):
        seen["n"] += 1
        if seen["n"] % 3 == 0:              # every third batch's answers
            pred = np.asarray(pred) + 0.05
        return pred

    def broken_init(self, *a, **kw):
        kw["postprocess"] = alter
        init(self, *a, **kw)

    monkeypatch.setattr(engine.ClusterServing, "__init__", broken_init)
    rc, line = _run(capsys, tiny.make_root(tmp_path), cell)
    assert seen["n"] > 0
    assert rc == 0 and line["correct"] is False, line


@pytest.mark.parametrize("cell", tiny.all_cells("train_epochs")[:1]
                         + tiny.all_cells("serve")[:1])
def test_control_and_planted_faults_read_above_the_program(cell, tmp_path):
    from benchmarks.harness import manifest, window
    c = manifest.Cell(cell, tiny.make_root(tmp_path))
    r = c.driver().control_readings(c, 5, 0.5, True,
                                    window.CompileListener())
    names = [k for k, lim in c.limits.items() if lim > 0]
    assert max(r["program"][k] for k in names) < tiny.TINY_LIMIT, r
    readings = [k for k in r if k.startswith(("control_", "fault_"))]
    assert any(k.startswith("control_") for k in readings)
    assert any(k.startswith("fault_") for k in readings)
    for name in readings:
        assert max(r[name][k] for k in names) > 3 * tiny.TINY_LIMIT, \
            (name, r[name])
