"""Orca-style Estimator — distributed fit/predict/evaluate on a TPU mesh.

This one class replaces the reference's entire execution-bridge + engine
stack (SURVEY.md §2.3/§2.4): where Analytics Zoo wrapped foreign graphs into
BigDL modules (TFTrainingHelper, zoo/.../tfpark/TFTrainingHelper.scala:33-309;
TorchModel, zoo/.../pipeline/api/net/TorchModel.scala:34-260) and synchronized
gradients through AllReduceParameter-over-BlockManager inside
InternalDistriOptimizer (zoo/.../keras/models/Topology.scala:1145-1550), here
the model is a flax module, the train step is one jitted function over a
``jax.sharding.Mesh``, and XLA emits the gradient collectives implied by the
sharding strategy (DP all-reduce, FSDP reduce-scatter/all-gather, TP
collectives) over ICI.

API parity targets:
- ``Estimator.from_keras`` / ``from_graph``  (ref pyzoo/zoo/orca/learn/tf/estimator.py:291,335)
- ``Estimator.from_torch``                   (ref pyzoo/zoo/orca/learn/pytorch/estimator.py:35)
- ``fit(data, epochs, batch_size, feature_cols, label_cols, validation_data,
  checkpoint_trigger)``, ``predict``, ``evaluate``, ``save``/``load``,
  ``load_orca_checkpoint``, ``get_train_summary``/``get_validation_summary``,
  ``set_constant_gradient_clipping``/``set_l2_norm_gradient_clipping``
  (ref pyzoo/zoo/orca/learn/spark_estimator.py:1-203)

Elastic retry-from-snapshot mirrors Topology.scala:1255-1337 (driver reloads
the latest checkpoint and resumes, up to ``failure_retry_times``).
"""

from __future__ import annotations

import glob
import json
import logging
import os
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from analytics_zoo_tpu.common import compile_ahead
from analytics_zoo_tpu.common import profiling as profiling_lib
from analytics_zoo_tpu.common import resilience, telemetry
from analytics_zoo_tpu.data.dataset import ShardedDataset, to_sharded_dataset
from analytics_zoo_tpu.data.shard import HostXShards, XShards
from analytics_zoo_tpu.learn import checkpoint as ckpt_lib
from analytics_zoo_tpu.learn import losses as loss_lib
from analytics_zoo_tpu.learn import metrics as metric_lib
from analytics_zoo_tpu.learn.optimizers import Optimizer
from analytics_zoo_tpu.learn.trigger import EveryEpoch, Trigger
from analytics_zoo_tpu.learn.trigger import fire as _fire_trigger
from analytics_zoo_tpu.parallel.strategy import ShardingStrategy

logger = logging.getLogger(__name__)


def _trigger_needs_score(trigger) -> bool:
    """True if the trigger (transitively) contains a MaxScore."""
    from analytics_zoo_tpu.learn.trigger import MaxScore
    if isinstance(trigger, MaxScore):
        return True
    return any(_trigger_needs_score(t)
               for t in getattr(trigger, "triggers", ()))


def _as_args(x):
    return x if isinstance(x, tuple) else (x,)


class _ProfileWindow:
    """Defers ``jax.profiler.start_trace`` until training enters a
    fit-relative step window and stops it when the window closes — whole-run
    traces of long fits are too large to open in TensorBoard/Perfetto, a
    20-step window is not. Thresholds are absolute ``_py_step`` values
    computed at fit start; ``on_step`` is called after every optimizer
    loop and ``close()`` from fit's ``finally``. Beside the trace it
    wrote, ``close()`` leaves ``scope_index.json``: for ``executable``,
    the step compiled ahead of time, which named scope every instruction
    belongs to (``profiling.scope_index``), so the trace's op events can
    be read by part of the model, and under ``counts`` its held values,
    erfc evaluations and mask generations (``profiling.step_counts``)."""

    def __init__(self, log_dir: str, start_step: int, stop_step: int,
                 executable: Optional[str] = None):
        if stop_step <= start_step:
            raise ValueError(
                f"profile_steps window must be non-empty, got "
                f"({start_step}, {stop_step})")
        self.log_dir = log_dir
        self.start_step = int(start_step)
        self.stop_step = int(stop_step)
        self.executable = executable
        self.active = False
        self.done = False

    def on_step(self, py_step: int):
        import jax
        if not self.active and not self.done and \
                py_step >= self.start_step:
            jax.profiler.start_trace(self.log_dir)
            self.active = True
            logger.info("jax profiler tracing steps [%d, %d) to %s",
                        self.start_step, self.stop_step, self.log_dir)
        if self.active and py_step >= self.stop_step:
            self.close()

    def close(self):
        if self.active:
            import jax
            jax.profiler.stop_trace()
            self.active = False
            self.done = True
            self._write_scope_index()

    def _write_scope_index(self):
        runs = sorted(glob.glob(
            os.path.join(self.log_dir, "plugins", "profile", "*")))
        index = self.executable and \
            profiling_lib.scope_index(self.executable)
        if not runs or not index:
            return
        counts = profiling_lib.step_counts(self.executable)
        with open(os.path.join(runs[-1], "scope_index.json"), "w") as fh:
            json.dump({self.executable: index,
                       "counts": {self.executable: counts}}, fh)


#: collections a module sows into anew every step; never model state
PER_STEP_COLLECTIONS = ("aux_loss", "counters")


class FlaxModelAdapter:
    """Uniform call surface over a flax.linen module: handles multi-input
    tuples, the optional ``train`` kwarg, dropout rngs and mutable
    collections (batch_stats)."""

    def __init__(self, module, sample_input, rng=None, params=None,
                 model_state=None):
        import jax
        self.module = module
        self.n_inputs = len(_as_args(sample_input))
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        self._takes_train = None
        if params is None:
            variables = self._init(rng, sample_input)
            variables = dict(variables)
            # a parameterless graph (e.g. a pure merge model) has no
            # "params" collection at all
            params = variables.pop("params", {})
            # "aux_loss" is a per-step sown output (e.g. MoE load-balance
            # loss), not persistent state — it is consumed by the train step
            # and must not ride model_state across steps (sow appends, so
            # carrying it would grow the collection every iteration)
            # so is "counters": numbers a module counts each step for
            # telemetry (the train step hands them out beside the loss)
            model_state = {k: v for k, v in variables.items()
                           if k not in PER_STEP_COLLECTIONS}
        self.params = params
        self.model_state = model_state or {}

    def _init(self, rng, sample_input):
        args = _as_args(sample_input)
        rngs = {"params": rng, "dropout": rng}
        try:
            out = self.module.init(rngs, *args, train=False)
            self._takes_train = True
            return out
        except TypeError:
            self._takes_train = False
            return self.module.init(rngs, *args)

    def apply(self, params, model_state, x, train: bool, rng):
        variables = {"params": params, **model_state}
        args = _as_args(x)
        kwargs = {}
        if self._takes_train:
            kwargs["train"] = train
        rngs = {"dropout": rng} if rng is not None else None
        if train:
            # "aux_loss" mutable lets sown per-step losses (MoE load
            # balancing) surface; the train step pops it off the returned
            # collections before they become the next model_state
            out, mut = self.module.apply(
                variables, *args, rngs=rngs,
                mutable=list(model_state.keys())
                + list(PER_STEP_COLLECTIONS), **kwargs)
            return out, dict(mut)
        out = self.module.apply(variables, *args, rngs=rngs, **kwargs)
        return out, model_state


class FnModelAdapter:
    """Adapter over a bare pure function — used by ``from_torch``
    (translated torch graphs) and ``from_fn``.

    Two conventions: without ``buffers`` the fn is
    ``apply_fn(params, *inputs)``; with ``buffers`` it is
    ``apply_fn({"params", "buffers"}, *inputs)`` and the buffers ride the
    estimator's model_state — frozen (no grads, no optimizer updates), which
    is how translated BatchNorm running statistics stay fixed."""

    def __init__(self, apply_fn, params, n_inputs: int, buffers=None,
                 supports_train: bool = False):
        self._fn = apply_fn
        self._variables_style = buffers is not None
        self._supports_train = supports_train
        self.params = params
        self.model_state = buffers or {}
        self.n_inputs = n_inputs

    def apply(self, params, model_state, x, train: bool, rng):
        if self._variables_style:
            kwargs = ({"train": train, "rng": rng}
                      if self._supports_train else {})
            out = self._fn({"params": params, "buffers": model_state},
                           *_as_args(x), **kwargs)
        else:
            out = self._fn(params, *_as_args(x))
        return out, model_state


class Estimator:
    """Factory façade (ref orca/learn/tf/estimator.py Estimator)."""

    @staticmethod
    def from_flax(*, model, loss, optimizer="adam", metrics=None,
                  sample_input, model_dir: Optional[str] = None,
                  strategy="dp", param_rules=None, seed: int = 0,
                  aux_loss_weight: float = 0.01, param_penalty=None,
                  backend: str = "tpu") -> "JaxEstimator":
        """Build an estimator from a flax.linen module.

        ``sample_input``: one example input (or tuple of inputs) with a
        batch dim of any size — used to initialise parameters and infer
        input structure (plays the role of the reference's TF graph export,
        tf_optimizer.py:252-287).
        """
        import jax
        adapter = FlaxModelAdapter(model, sample_input,
                                   rng=jax.random.PRNGKey(seed))
        return JaxEstimator(adapter, loss=loss, optimizer=optimizer,
                            metrics=metrics, model_dir=model_dir,
                            strategy=strategy, param_rules=param_rules,
                            seed=seed, aux_loss_weight=aux_loss_weight,
                            param_penalty=param_penalty)

    @staticmethod
    def from_torch(*, model, loss, optimizer="adam", metrics=None,
                   sample_input, model_dir: Optional[str] = None,
                   strategy="dp", param_rules=None, seed: int = 0
                   ) -> "JaxEstimator":
        """Train a PyTorch ``nn.Module`` on the TPU mesh
        (ref pyzoo/zoo/orca/learn/pytorch/estimator.py:35 Estimator.from_torch).

        The reference runs torch itself inside executors (Jep/DDP); here the
        module is translated to a pure jax function (net/torch_net.py) so
        the SAME pjit train step applies — grads flow through the translated
        graph, not through torch autograd."""
        from analytics_zoo_tpu.net.torch_net import torch_to_jax
        apply_fn, variables = torch_to_jax(model)
        adapter = FnModelAdapter(apply_fn, variables["params"],
                                 len(_as_args(sample_input)),
                                 buffers=variables["buffers"],
                                 supports_train=True)
        return JaxEstimator(adapter, loss=loss, optimizer=optimizer,
                            metrics=metrics, model_dir=model_dir,
                            strategy=strategy, param_rules=param_rules,
                            seed=seed)

    @staticmethod
    def from_fn(*, apply_fn, params, loss, optimizer="adam", metrics=None,
                n_inputs: int = 1, model_dir: Optional[str] = None,
                strategy="dp", param_rules=None, seed: int = 0
                ) -> "JaxEstimator":
        """Escape hatch: any pure ``apply_fn(params, *inputs)``."""
        adapter = FnModelAdapter(apply_fn, params, n_inputs)
        return JaxEstimator(adapter, loss=loss, optimizer=optimizer,
                            metrics=metrics, model_dir=model_dir,
                            strategy=strategy, param_rules=param_rules,
                            seed=seed)

    # reference-compatible spellings
    @staticmethod
    def from_keras(*, keras_model, loss=None, optimizer=None,
                   metrics=None, model_dir: Optional[str] = None,
                   strategy=None, param_rules=None) -> "JaxEstimator":
        """Estimator over a zoo-keras model
        (ref pyzoo/zoo/orca/learn/tf/estimator.py:335 Estimator.from_keras).
        Settings already on the model (a prior ``compile``, a prior
        ``set_strategy``) are kept; explicit non-None arguments override."""
        from analytics_zoo_tpu.keras.models import KerasNet
        model = getattr(keras_model, "model", keras_model)  # ZooModel wrap
        if not isinstance(model, KerasNet):
            raise TypeError(
                f"from_keras expects a zoo keras model, got "
                f"{type(keras_model).__name__}; use from_flax for raw "
                "flax modules")
        compiled = model._compile_args or {}
        if loss is None and compiled.get("loss") is None:
            raise ValueError(
                "no loss: pass loss=... or compile the model first (every "
                "other training entry point errors here too)")
        if strategy is not None or param_rules is not None:
            model.set_strategy(strategy or model._strategy,
                               param_rules=param_rules)
        model.compile(
            optimizer=optimizer if optimizer is not None
            else compiled.get("optimizer", "adam"),
            loss=loss if loss is not None else compiled["loss"],
            metrics=metrics if metrics is not None
            else compiled.get("metrics"))
        est = model._ensure_estimator(for_training=True)
        if model_dir:
            est.model_dir = model_dir
        return est

    @staticmethod
    def from_graph(*, inputs, outputs, loss, optimizer="adam",
                   metrics=None, model_dir: Optional[str] = None,
                   strategy="dp", param_rules=None) -> "JaxEstimator":
        """Estimator over a symbolic layer graph — Input()/layer Nodes
        (ref orca/learn/tf/estimator.py:291 Estimator.from_graph, which
        takes TF1 graph tensors; here the graph is the zoo keras graph)."""
        from analytics_zoo_tpu.keras.models import Model
        model = Model(inputs, outputs)
        return Estimator.from_keras(
            keras_model=model, loss=loss, optimizer=optimizer,
            metrics=metrics, model_dir=model_dir, strategy=strategy,
            param_rules=param_rules)

    @staticmethod
    def latest_checkpoint(model_dir: str):
        found = ckpt_lib.find_latest_checkpoint(model_dir)
        return found[0] if found else None


class JaxEstimator:
    """The engine (ref TensorFlowEstimator orca/learn/tf/estimator.py:429 +
    Scala Estimator zoo/.../pipeline/estimator/Estimator.scala:68-309)."""

    def __init__(self, adapter: FlaxModelAdapter, loss, optimizer,
                 metrics=None, model_dir: Optional[str] = None,
                 strategy="dp", param_rules=None, seed: int = 0,
                 aux_loss_weight: float = 0.01, param_penalty=None):
        import jax

        self.adapter = adapter
        # optional pure params→scalar regularization penalty added to the
        # training objective (keras W/b regularizers; ref BigDL applies
        # these inside the optimizer)
        self.param_penalty = param_penalty
        self.loss_fn = loss_lib.get(loss)
        self.optimizer = Optimizer.get(optimizer)
        self.metrics = [metric_lib.get(m) for m in (metrics or [])]
        self.model_dir = model_dir
        self.strategy = ShardingStrategy.parse(strategy, param_rules=param_rules)
        self.seed = seed
        # weight on sown "aux_loss" values (MoE load balancing; Switch
        # Transformer uses 0.01) — added to the data loss in the train step
        self.aux_loss_weight = float(aux_loss_weight)
        self.failure_retry_times = 5  # ref Topology.scala:1256 bigdl.failure.retryTimes

        self._grad_clip = None  # ("norm", v) | ("const", min, max)
        self._mesh = None
        self._state = None
        self._train_step = None
        self._eval_step = None
        self._predict_fn = None
        self._precompile_thread = None
        # fit's decomposition on the host's clock; its FLOP count is the
        # ahead-of-time executable's (_start_precompile)
        self._step_prof = profiling_lib.StepProfiler(name="train")
        self._epoch = 0
        self._py_step = 0  # host-side mirror of state["step"]: no device sync
        self._train_writer = None
        self._val_writer = None
        self._tb_dirs = None
        self._base_rng = jax.random.PRNGKey(seed + 17)

    # ------------- gradient clipping (ref spark_estimator.py:150-180) ----
    def set_constant_gradient_clipping(self, min_value: float, max_value: float):
        self._grad_clip = ("const", float(min_value), float(max_value))
        self._on_tx_changed()

    def set_l2_norm_gradient_clipping(self, clip_norm: float):
        self._grad_clip = ("norm", float(clip_norm))
        self._on_tx_changed()

    def clear_gradient_clipping(self):
        self._grad_clip = None
        self._on_tx_changed()

    def _on_tx_changed(self):
        """The optax chain changed shape — rebuild opt_state around the
        current params (training progress in params/step is kept)."""
        self._train_step = None
        if self._state is not None:
            import jax
            tx = self._tx()
            params = self._state["params"]
            host_params = jax.device_get(params)
            new_opt = self._unalias_opt_state(tx.init(host_params),
                                              host_params)
            state = dict(self._state)
            state["opt_state"] = new_opt
            shardings = self._state_shardings(
                {"step": state["step"], "params": jax.device_get(params),
                 "opt_state": new_opt, "model_state": state["model_state"]},
                self._ensure_mesh())
            self._state = jax.device_put(jax.device_get(state), shardings)
            self._state_sharding_tree = shardings

    # ------------- summaries (ref estimator.py:167-220) ------------------
    def set_tensorboard(self, log_dir: str, app_name: str):
        self._tb_dirs = (os.path.join(log_dir, app_name, "train"),
                         os.path.join(log_dir, app_name, "validation"))
        if self._train_writer is not None:  # redirect future events
            self._train_writer.close()
            self._val_writer.close()
            self._train_writer = self._val_writer = None

    def _writers(self):
        from analytics_zoo_tpu.common.summary import SummaryWriter
        if self._train_writer is None:
            if self._tb_dirs is None:
                base = self.model_dir or os.path.join(".", "zoo_tpu_logs")
                self._tb_dirs = (os.path.join(base, "train"),
                                 os.path.join(base, "validation"))
            self._train_writer = SummaryWriter(self._tb_dirs[0])
            self._val_writer = SummaryWriter(self._tb_dirs[1])
        return self._train_writer, self._val_writer

    def get_train_summary(self, tag: str):
        """("Loss" | "Throughput" | "LearningRate"...) → [(step, value)]
        (ref Topology.scala:208-240)."""
        return self._train_writer.get_scalar(tag) if self._train_writer else []

    def get_validation_summary(self, tag: str):
        return self._val_writer.get_scalar(tag) if self._val_writer else []

    # ------------- compile machinery -------------------------------------
    def _tx(self):
        import jax
        import optax

        def scoped(name, inner):
            # the same transformation, its update's ops named in the
            # compiled step (profiling.scope_index reads the names)
            def update(updates, state, params=None):
                with jax.named_scope(name):
                    return inner.update(updates, state, params)
            return optax.GradientTransformation(inner.init, update)

        tx = scoped("optimizer", self.optimizer.to_optax())
        if self._grad_clip:
            if self._grad_clip[0] == "norm":
                clip = optax.clip_by_global_norm(self._grad_clip[1])
            else:
                lo, hi = self._grad_clip[1], self._grad_clip[2]
                mag = max(abs(lo), abs(hi))
                clip = optax.clip(mag)
            tx = optax.chain(scoped("clip", clip), tx)
        return tx

    def _ensure_mesh(self):
        if self._mesh is None:
            from analytics_zoo_tpu.parallel import mesh as mesh_lib
            needed = set(self.strategy.axis_names())
            cur = mesh_lib.get_default_mesh()
            if set(cur.axis_names) >= needed:
                self._mesh = cur
            else:
                self._mesh = self.strategy.build_mesh()
        return self._mesh

    @staticmethod
    def _unalias_opt_state(opt_state, params):
        """Some optax states alias buffers — either the passed params
        (lbfgs keeps the previous params) or each other (jax dedupes the
        identical zeros arrays lbfgs uses for its history buffers). The
        train step donates the whole state, and XLA rejects the same
        buffer donated twice — copy every repeated leaf."""
        import jax
        seen = {id(leaf) for leaf in jax.tree_util.tree_leaves(params)}

        def uniq(leaf):
            if id(leaf) in seen:
                leaf = leaf.copy()
            seen.add(id(leaf))
            return leaf

        return jax.tree_util.tree_map(uniq, opt_state)

    def _init_state(self):
        import jax
        if self._state is not None:
            return
        mesh = self._ensure_mesh()
        tx = self._tx()
        params = self.adapter.params
        opt_state = self._unalias_opt_state(tx.init(params), params)
        state = {"step": np.zeros((), np.int32),
                 "params": params,
                 "opt_state": opt_state,
                 "model_state": self.adapter.model_state}
        shardings = self._state_shardings(state, mesh)
        self._state = jax.device_put(state, shardings)
        self._state_sharding_tree = shardings

    def _state_shardings(self, state, mesh):
        """Sharding pytree for the full train state. Optimizer-state leaves
        inherit the sharding of the parameter whose path suffix they carry
        (so FSDP shards Adam moments exactly like weights — the analog of the
        reference's per-partition weight-range ownership,
        Topology.scala:1094-1104)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        param_specs = {}
        flat, _ = jax.tree_util.tree_flatten_with_path(state["params"])
        for path, leaf in flat:
            p = _path_str(path)
            param_specs[p] = self.strategy.param_spec(p, leaf.shape, mesh)

        def spec_for(path_str, leaf):
            for p, spec in param_specs.items():
                # '/'-boundary suffix match so 'q_proj/kernel' never matches
                # a rule for 'proj/kernel'
                if (path_str == p or path_str.endswith("/" + p)) \
                        and np.shape(leaf) and \
                        tuple(np.shape(leaf)) == tuple(np.shape(_get_by_path(
                            state["params"], p))):
                    return spec
            return P()

        flat_state, treedef = jax.tree_util.tree_flatten_with_path(state)
        out = []
        for path, leaf in flat_state:
            ps = _path_str(path)
            if ps.startswith("params/"):
                spec = param_specs.get(ps[len("params/"):], P())
            elif ps.startswith("opt_state"):
                spec = spec_for(ps, leaf)
            else:
                spec = P()
            out.append(NamedSharding(mesh, spec))
        return jax.tree_util.tree_unflatten(treedef, out)

    def _build_train_step(self):
        import jax
        import jax.numpy as jnp
        import optax

        if self._train_step is not None:
            return
        self._init_state()
        tx = self._tx()
        adapter, loss_fn, base_rng = self.adapter, self.loss_fn, self._base_rng
        aux_weight = self.aux_loss_weight
        penalty_fn = self.param_penalty

        # jax.named_scope names the phases no flax module names (loss,
        # clip and optimizer — the latter two inside tx — and metrics):
        # the names ride the compiled step's HLO metadata, where
        # profiling.scope_index finds them; they cost nothing at run time
        def step_fn(state, x, y):
            rng = jax.random.fold_in(base_rng, state["step"])

            def compute_loss(params):
                preds, new_mut = adapter.apply(params, state["model_state"],
                                               x, True, rng)
                with jax.named_scope("loss"):
                    per = loss_fn(y, preds)
                    loss = per.mean()
                    if penalty_fn is not None:
                        loss = loss + penalty_fn(params)
                    # consume sown per-step losses (MoE load balance):
                    # they add to the objective and are stripped so
                    # model_state keeps its across-step structure
                    if isinstance(new_mut, dict) and "aux_loss" in new_mut:
                        new_mut = dict(new_mut)
                        aux = new_mut.pop("aux_loss")
                        aux_terms = [
                            jnp.sum(jnp.asarray(leaf))
                            for leaf in jax.tree_util.tree_leaves(aux)]
                        if aux_terms:
                            loss = loss + aux_weight * sum(aux_terms)
                counters = {}
                if isinstance(new_mut, dict) and "counters" in new_mut:
                    new_mut = dict(new_mut)
                    counters = new_mut.pop("counters")
                return loss, (new_mut, counters)

            (loss_val, (new_mut, counters)), grads = jax.value_and_grad(
                compute_loss, has_aux=True)(state["params"])
            updates, new_opt = tx.update(grads, state["opt_state"],
                                         state["params"])
            with jax.named_scope("optimizer"):
                new_params = optax.apply_updates(state["params"], updates)
            with jax.named_scope("metrics"):
                new_state = {"step": state["step"] + 1,
                             "params": new_params,
                             "opt_state": new_opt,
                             "model_state": new_mut}
                logs = {"loss": loss_val.astype(jnp.float32)}
                if counters:
                    # what the modules counted this step: outputs of the
                    # step, fetched where the losses are (flush_window)
                    logs["counters"] = counters
            return new_state, logs

        # instrument_jit = jax.jit + recompile accounting: the
        # zoo_jit_cache_misses_total{fn=...} counter stays flat across
        # steady-state steps and increments exactly when the avals
        # signature changes (new batch bucket, dtype drift)
        self._train_step = telemetry.instrument_jit(
            step_fn, name="estimator_train_step", donate_argnums=0)

        def scan_fn(state, batches):
            # K steps in ONE dispatch: for small models per-step launch
            # overhead dominates, and scan amortizes it (the analog of the
            # reference keeping its hot loop inside the JVM task,
            # Topology.scala:1262 optimizeModels)
            def body(s, xy):
                s2, logs = step_fn(s, xy[0], xy[1])
                return s2, logs["loss"]

            state, losses = jax.lax.scan(body, state, batches)
            return state, losses

        self._train_scan = telemetry.instrument_jit(
            scan_fn, name="estimator_train_scan", donate_argnums=0)

        def epoch_fn(state, x_full, y_full, key, bs, do_shuffle):
            # HBM-cached epoch: the WHOLE dataset is device-resident, the
            # permutation is drawn on device, and every optimizer step of
            # the epoch runs in one compiled dispatch — the "HBM tier"
            # counterpart of the reference's DRAM FeatureSet, sized for
            # datasets that fit on-chip (NCF/tabular scale). Nothing but
            # one PRNG key crosses the host↔device link per epoch.
            n = jax.tree_util.tree_leaves(x_full)[0].shape[0]
            n_steps = n // bs
            order = jax.random.permutation(key, n) if do_shuffle \
                else jnp.arange(n)
            idx = order[:n_steps * bs].reshape(n_steps, bs)

            def body(s, ib):
                bx = jax.tree_util.tree_map(lambda a: a[ib], x_full)
                by = jax.tree_util.tree_map(lambda a: a[ib], y_full)
                s2, logs = step_fn(s, bx, by)
                return s2, logs["loss"]

            state, losses = jax.lax.scan(body, state, idx)
            return state, losses

        self._train_epoch_cached = telemetry.instrument_jit(
            epoch_fn, name="estimator_epoch_cached", donate_argnums=0,
            static_argnums=(4, 5))

    def _build_eval_step(self):
        import jax
        import jax.numpy as jnp

        if self._eval_step is not None:
            return
        adapter, loss_fn, metrics = self.adapter, self.loss_fn, self.metrics

        def eval_fn(state, metric_states, x, y, mask):
            preds, _ = adapter.apply(state["params"], state["model_state"],
                                     x, False, None)
            per = loss_fn(y, preds)
            m = jnp.ones_like(per) if mask is None else mask
            loss_sum = (per * m).sum()
            new_states = [metric.update(ms, y, preds, mask)
                          for metric, ms in zip(metrics, metric_states)]
            return new_states, loss_sum, m.sum()

        self._eval_step_masked = jax.jit(eval_fn, static_argnames=())
        self._eval_step = jax.jit(
            lambda s, ms, x, y: eval_fn(s, ms, x, y, None))

    def _build_predict(self):
        import jax
        if self._predict_fn is not None:
            return
        adapter = self.adapter

        def pred_fn(state, x):
            preds, _ = adapter.apply(state["params"], state["model_state"],
                                     x, False, None)
            return preds

        self._predict_fn = telemetry.instrument_jit(
            pred_fn, name="estimator_predict")

    def _start_precompile(self, ds, batch_size: int,
                          steps_per_loop: int = 1,
                          with_eval: bool = False):
        """AOT-compile the train (scan/eval) steps on a background daemon
        thread, concurrently with first-batch staging. The AOT build seeds
        JAX's persistent compilation cache, so the hot loop's first jit
        dispatch deserializes the executable instead of compiling it —
        step 0 overlaps compile with data load. Entirely best-effort: any
        failure (streaming dataset with no materialized shapes, exotic
        shardings) leaves the plain jit path untouched. Returns the
        warmup thread, or None when there was nothing to precompile."""
        import threading

        import jax

        if getattr(ds, "x", None) is None:
            # streaming datasets hold no whole-dataset tensors to derive
            # avals from (x is None; tree_map would silently produce None
            # avals and warm a step that crashes on them) — the hot loop's
            # plain jit path handles the first window instead
            logger.debug("step precompile skipped: streaming dataset")
            return None
        bs = int(batch_size)

        def batched(extra_lead):
            from jax.sharding import NamedSharding, PartitionSpec as P
            mesh = self._ensure_mesh()

            def f(a):
                shape = getattr(a, "shape", None)
                dtype = getattr(a, "dtype", None)
                if shape is None or dtype is None:
                    raise TypeError("dataset tensors are not materialized")
                shp = tuple(extra_lead) + (bs,) + tuple(shape[1:])
                # the hot loop feeds committed mesh-placed batches
                # (device_iterator/device_scan_iterator shard the batch
                # dim per the strategy, scan lead unsharded); an aval
                # without that sharding lowers a different executable,
                # so the "precompiled" step silently recompiles on its
                # first real batch
                base = self.strategy.batch_spec(len(shp) - len(extra_lead))
                spec = P(*([None] * len(extra_lead)), *base) \
                    if extra_lead else base
                return jax.ShapeDtypeStruct(
                    shp, dtype, sharding=NamedSharding(mesh, spec))
            return f

        def state_avals(with_sharding: bool):
            def f(a):
                if with_sharding:
                    sh = getattr(a, "sharding", None)
                    if sh is not None:
                        return jax.ShapeDtypeStruct(
                            a.shape, a.dtype, sharding=sh)
                arr = a if hasattr(a, "shape") else np.asarray(a)
                return jax.ShapeDtypeStruct(
                    tuple(arr.shape), arr.dtype)
            return jax.tree_util.tree_map(f, self._state)

        try:
            x_avals = jax.tree_util.tree_map(batched(()), ds.x)
            y_avals = jax.tree_util.tree_map(batched(()), ds.y)
            targets = []
            if steps_per_loop > 1:
                k = int(steps_per_loop)
                scan_x = jax.tree_util.tree_map(batched((k,)), ds.x)
                scan_y = jax.tree_util.tree_map(batched((k,)), ds.y)
                targets.append(("estimator_train_scan", self._train_scan,
                                ((scan_x, scan_y),), k))
            else:
                targets.append(("estimator_train_step", self._train_step,
                                (x_avals, y_avals), 1))
            if with_eval and self._eval_step is not None:
                ms = [m.init_state() for m in self.metrics]
                ms_avals = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(
                        np.shape(a), np.asarray(a).dtype), ms)
                targets.append(("estimator_eval_step", self._eval_step,
                                (ms_avals, x_avals, y_avals), 0))
        except Exception:
            logger.debug("step precompile skipped: dataset shapes "
                         "unavailable", exc_info=True)
            return None

        step_prof = self._step_prof

        def worker():
            # the eval step takes the state WITHOUT donating it, the train
            # step donates — but the aval signature is identical, so one
            # state tree serves every target
            for sharded in (True, False):
                sa = state_avals(sharded)
                ok = True
                for name, fn, rest, train_steps in targets:
                    if compile_ahead.draining():
                        return          # interpreter exit: stop compiling
                    cache = compile_ahead.ExecutableCache(fn, name=name)
                    if not cache.warm(sa, *rest):
                        ok = False
                        break
                    if train_steps:
                        # zoo_step_flops: XLA's count for the executable
                        # just built, no lowering of its own
                        step_prof.set_flops(cache.flops, train_steps)
                if ok:
                    return

        t = threading.Thread(target=worker, daemon=True,
                             name="zoo-warmup-estimator")
        t.start()
        compile_ahead.register_warmup_thread(t)
        self._precompile_thread = t
        return t

    # ------------- public API --------------------------------------------
    def fit(self, data, epochs: int = 1, batch_size: int = 32,
            feature_cols: Optional[Sequence[str]] = None,
            label_cols: Optional[Sequence[str]] = None,
            validation_data=None,
            checkpoint_trigger: Optional[Trigger] = None,
            summary_interval: int = 20,
            shuffle: bool = True,
            steps_per_loop: int = 1,
            cache: Optional[str] = None,
            profile: bool = False,
            profile_steps: Optional[Sequence[int]] = None,
            auto_resume: bool = False
            ) -> Dict[str, List[float]]:
        """(ref orca/learn/tf/estimator.py fit:486; batch_size is the GLOBAL
        batch — the reference required batch_size % num_workers == 0, here it
        must divide the data-axis size of the mesh).

        ``steps_per_loop > 1`` fuses that many optimizer steps into one
        compiled ``lax.scan`` dispatch — a large win for small models where
        per-step launch overhead dominates. Checkpoint triggers are then
        evaluated once per loop, not per step.

        ``cache="device"`` keeps the whole dataset resident in HBM and runs
        EACH EPOCH as one compiled dispatch with an on-device shuffle — the
        HBM analog of the reference's DRAM FeatureSet tier, for datasets
        that fit on-chip. Requires an unsharded batch (single device or no
        data axis); loss summaries flush once per epoch.

        ``profile=True`` runs ``jax.profiler`` tracing over a bounded
        fit-relative step window — ``profile_steps=(start, stop)``, default
        ``(0, 20)`` — instead of the whole run, so the dump stays small
        enough to actually open. Passing ``profile_steps`` alone implies
        ``profile=True``. Trace files land in
        ``<tensorboard dir>/plugins/profile`` next to the TF-events
        summaries, viewable in TensorBoard's profile tab or Perfetto.

        The trace directory also gets ``scope_index.json``: which
        named scope (flax module path, ``loss``, ``optimizer`` ...) each
        instruction of the compiled step belongs to, so the trace's op
        events can be summed by part of the model.

        Independently of ``profile``, every fit publishes its
        decomposition through the telemetry registry, on the host's clock
        and without a fence of its own: the ``zoo_train_phase_seconds``
        histogram (per step ``data_wait``/``dispatch``/``callback``; per
        fit ``prepare``; per epoch ``first_batch``; per summary flush
        ``flush`` and ``device``, the window's seconds per step) and, at
        each flush, ``zoo_mfu`` and ``zoo_hbm_bytes``; ``zoo_step_flops``
        is XLA's ``cost_analysis`` of the ahead-of-time executable. The
        same intervals are tracer spans (``fit`` > ``fit/prepare``,
        ``epoch`` > ``epoch/first_batch``, ``epoch/flush``,
        ``fit/validate``, ``fit/checkpoint``) and, while a
        ``jax.profiler`` session is open, ``zoo:<name>`` annotations on
        its clock, with ``zoo:data_wait`` and ``zoo:dispatch`` per step —
        see docs/observability.md.

        ``auto_resume=True`` hardens the retry-from-snapshot boundary for
        backend loss (a wedged/lost accelerator, or an injected
        ``ZOO_FAULT_PLAN`` fault): the reload goes through
        ``load_latest_checkpoint`` — which validates each version against
        the live state and walks past corrupt ones — the retry budget is
        ``ZOO_FIT_MAX_RESUMES`` (default ``failure_retry_times``), and the
        failure is reported to the backend supervisor when one is
        running. Step/epoch counters and data order restore exactly, so a
        resumed run converges to the bitwise-identical loss of an
        unfaulted one."""
        tracer = telemetry.get_tracer()
        with tracer.span("fit", trace_id=f"train/fit-{self._py_step}"):
            with self._step_prof.phase("fit/prepare", "prepare"):
                ds = self._coerce(
                    to_sharded_dataset(data, feature_cols, label_cols))
                val_ds = (self._coerce(to_sharded_dataset(
                    validation_data, feature_cols, label_cols))
                    if validation_data is not None else None)
                mesh = self._ensure_mesh()
                self._build_train_step()
                if val_ds is not None:
                    self._build_eval_step()
                # compile-ahead: AOT-build the train (and eval) step on a
                # daemon thread WHILE the first batch stages host-side —
                # step 0's jit call then deserializes from the persistent
                # compile cache instead of compiling cold (ISSUE 5
                # tentpole, third hot path)
                self._start_precompile(ds, batch_size, steps_per_loop,
                                       with_eval=val_ds is not None)
            return self._fit_epochs(
                ds, val_ds, mesh, epochs=epochs, batch_size=batch_size,
                checkpoint_trigger=checkpoint_trigger,
                summary_interval=summary_interval, shuffle=shuffle,
                steps_per_loop=steps_per_loop, cache=cache,
                profile=profile, profile_steps=profile_steps,
                auto_resume=auto_resume)

    def _fit_epochs(self, ds, val_ds, mesh, *, epochs, batch_size,
                    checkpoint_trigger, summary_interval, shuffle,
                    steps_per_loop, cache, profile, profile_steps,
                    auto_resume) -> Dict[str, List[float]]:
        """``fit`` after ``fit/prepare``: the epoch loop with its retries,
        validation and checkpoints."""
        tracer = telemetry.get_tracer()
        if checkpoint_trigger is None and self.model_dir:
            checkpoint_trigger = EveryEpoch()
        if checkpoint_trigger is not None and \
                _trigger_needs_score(checkpoint_trigger) and val_ds is None:
            warnings.warn(
                "checkpoint_trigger contains MaxScore but fit() got no "
                "validation_data — the trigger can never fire and no "
                "checkpoints will be written")

        train_writer, _ = self._writers()
        history: Dict[str, List[float]] = {"loss": []}
        retries = 0
        target_epoch = self._epoch + epochs

        profile_window = None
        if profile or profile_steps is not None:
            lo, hi = profile_steps if profile_steps is not None else (0, 20)
            profile_window = _ProfileWindow(
                self._tb_dirs[0], self._py_step + int(lo),
                self._py_step + int(hi),
                executable=("estimator_train_scan" if steps_per_loop > 1
                            else "estimator_train_step"))
        # which steps get a train/step-N trace
        self._step_prof.sample_every = max(2, summary_interval // 2)

        try:
            while self._epoch < target_epoch:
                try:
                    with tracer.span("epoch"):
                        epoch_loss = self._run_epoch(
                            ds, mesh, batch_size, shuffle, summary_interval,
                            train_writer, checkpoint_trigger,
                            steps_per_loop=steps_per_loop, cache=cache,
                            profile_window=profile_window)
                except Exception as e:
                    # elastic retry-from-snapshot (ref Topology.scala:1255-1337)
                    retries += 1
                    limit = self.failure_retry_times
                    if auto_resume:
                        resilience.note_backend_loss(e)
                        limit = resilience.fit_max_resumes(limit)
                    if not self.model_dir or retries > limit:
                        raise
                    if auto_resume:
                        # validated reload: walks past torn/corrupt
                        # versions instead of resuming into garbage
                        path = self._auto_resume_reload()
                        if path is None:
                            raise
                    else:
                        found = ckpt_lib.find_latest_checkpoint(
                            self.model_dir)
                        if found is None:
                            raise
                        path = found[0]
                        self.load_orca_checkpoint(path)
                    logger.exception(
                        "training step failed; retry %d/%d from %s",
                        retries, limit, path)
                    continue
                history["loss"].append(epoch_loss)
                self._epoch += 1
                val_score = None
                if val_ds is not None:
                    with tracer.span("fit/validate"):
                        val = self.evaluate(val_ds, batch_size=batch_size)
                    for k, v in val.items():
                        history.setdefault("val_" + k, []).append(v)
                        self._val_writer.add_scalar(k, v, self._py_step)
                    # the full metrics dict feeds the triggers: MaxScore
                    # picks its named metric (or the first non-loss one,
                    # warning when that is error-style)
                    val_score = val
                if checkpoint_trigger and self.model_dir and \
                        _fire_trigger(checkpoint_trigger, self._epoch,
                                      self._py_step, epoch_loss, val_score):
                    with tracer.span("fit/checkpoint"):
                        self._save_snapshot()
        finally:
            if profile_window is not None:
                profile_window.close()
        train_writer.flush()
        if self._val_writer:
            self._val_writer.flush()
        return history

    def _coerce(self, ds: ShardedDataset) -> ShardedDataset:
        """If the model is single-input but feature_cols produced one input
        per column (the reference's DataFrame convention,
        tf_dataset.py:1200 DataFrameDataset), stack scalar columns into one
        feature matrix."""
        if (self.adapter.n_inputs == 1 and isinstance(ds.x, tuple)
                and all(np.ndim(a) == 1 for a in ds.x)):
            x = np.column_stack([np.asarray(a) for a in ds.x])
            return ShardedDataset(x, ds.y)
        return ds

    def _iteration(self) -> int:
        return int(np.asarray(self._state["step"]))

    def _current_lr(self, step: int) -> Optional[float]:
        """Best-effort current learning rate: the optimizer wrappers carry
        ``lr`` (+ optional ``schedule``); optax schedules are callables of
        the step. None when the optimizer doesn't expose one (raw optax
        transforms)."""
        from analytics_zoo_tpu.learn.optimizers import _lr as resolve_lr
        opt = self.optimizer
        base = getattr(opt, "lr", None)
        if base is None:
            return None
        try:
            val = resolve_lr(base, getattr(opt, "schedule", None))
            return float(val(step)) if callable(val) else float(val)
        except Exception:
            return None

    def _mirror_train_scalars(self, writer, step: int, loss: float,
                              throughput: float, step_seconds: float):
        """One window's training scalars go BOTH ways: TF-events (the
        existing TensorBoard surface) and the telemetry registry (the
        Prometheus surface) — same numbers, one call site."""
        reg = telemetry.get_registry()
        reg.gauge("zoo_training_loss",
                  "Last flushed training loss").set(loss)
        reg.gauge("zoo_training_throughput_samples_per_sec",
                  "Training throughput over the last summary window"
                  ).set(throughput)
        reg.histogram("zoo_training_step_seconds",
                      "Mean per-step wall time per summary window"
                      ).observe(step_seconds)
        lr = self._current_lr(step)
        if lr is not None:
            writer.add_scalar("LearningRate", lr, step)
            reg.gauge("zoo_training_learning_rate",
                      "Learning rate at the last flushed step").set(lr)

    def _run_epoch_cached(self, ds, mesh, batch_size, shuffle,
                          writer) -> float:
        """One fused on-device epoch over the HBM-resident dataset."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if getattr(ds, "x", None) is None or ds.y is None:
            raise ValueError("cache='device' needs a materialized labelled "
                             "dataset (streaming/tiered feeds stay on the "
                             "standard path)")
        from analytics_zoo_tpu.parallel import mesh as mesh_lib

        for ax in self.strategy.batch_axes():
            size = mesh_lib.mesh_axis_size(mesh, ax)
            if size > 1:
                raise ValueError(
                    "cache='device' needs an unsharded batch (single "
                    f"device or batch axis size 1); {ax}={size}. Use the "
                    "standard feed for data-parallel meshes.")
        # strong ref: id() alone could alias a NEW dataset allocated at a
        # freed dataset's address and silently train on stale device data
        if getattr(self, "_cached_ds", None) is not ds:
            repl = NamedSharding(mesh, P())
            self._cached_x = telemetry.traced_device_put(ds.x, repl)
            self._cached_y = telemetry.traced_device_put(ds.y, repl)
            self._cached_ds = ds
        key = jax.random.fold_in(self._base_rng, 977 + self._epoch)
        n_steps = ds.n // batch_size
        if n_steps < 1:
            raise ValueError(f"batch_size {batch_size} > dataset {ds.n}")
        t0 = time.perf_counter()
        self._state, losses = self._train_epoch_cached(
            self._state, self._cached_x, self._cached_y, key,
            int(batch_size), bool(shuffle))
        t_fetch = time.perf_counter()
        losses = np.asarray(telemetry.traced_device_get(losses), np.float64)
        dt = time.perf_counter() - t0
        # the fetch is the only host-blocked part of the fused epoch —
        # everything before it is one async dispatch
        telemetry.observe_device_block(time.perf_counter() - t_fetch,
                                       "train_epoch_cached")
        self._py_step += n_steps
        throughput = n_steps * batch_size / max(dt, 1e-9)
        writer.add_scalar("Loss", float(losses[-1]), self._py_step)
        writer.add_scalar("Throughput", throughput, self._py_step)
        self._mirror_train_scalars(writer, self._py_step,
                                   float(losses[-1]), throughput,
                                   dt / max(n_steps, 1))
        logger.info("cached epoch %d: %d steps in %.3fs (%.0f samples/s)",
                    self._epoch, n_steps, dt,
                    n_steps * batch_size / max(dt, 1e-9))
        return float(losses.mean())

    def _run_epoch(self, ds, mesh, batch_size, shuffle, summary_interval,
                   writer, checkpoint_trigger, steps_per_loop: int = 1,
                   cache: Optional[str] = None,
                   profile_window=None) -> float:
        if cache == "device":
            return self._run_epoch_cached(ds, mesh, batch_size, shuffle,
                                          writer)
        if cache is not None:
            raise ValueError(f"unknown cache mode {cache!r} "
                             "(supported: 'device')")
        step_prof = self._step_prof
        losses: List[Any] = []
        pending: List[Any] = []
        pending_counters: List[Any] = []
        pending_steps = 0
        t_epoch = time.perf_counter()
        samples = 0
        t_window = time.perf_counter()

        def flush_window():
            # one host sync per window: fetch the buffered device scalars
            nonlocal pending, pending_counters, pending_steps, t_window
            if not pending:
                return
            with step_prof.phase("epoch/flush", "flush"):
                t_fetch = time.perf_counter()
                fetched, counted = telemetry.traced_device_get(
                    (pending, pending_counters))
                vals = list(np.concatenate(
                    [np.atleast_1d(np.asarray(v)) for v in fetched]
                ).astype(float))
                telemetry.observe_device_block(
                    time.perf_counter() - t_fetch, "train_flush")
                telemetry.publish_step_counters(counted)
                losses.extend(vals)
                step = self._py_step
                writer.add_scalar("Loss", vals[-1], step)
                dt = time.perf_counter() - t_window
                throughput = pending_steps * batch_size / max(dt, 1e-9)
                writer.add_scalar("Throughput", throughput, step)
                self._mirror_train_scalars(writer, step, vals[-1],
                                           throughput,
                                           dt / max(pending_steps, 1))
                # the fetch above was the sync: MFU, HBM and seconds per
                # step over this window cost no fence of their own
                step_prof.observe_window(pending_steps, dt)
            t_window = time.perf_counter()
            pending = []
            pending_counters = []
            pending_steps = 0

        def after_steps(n_steps):
            nonlocal pending_steps, samples
            start = self._py_step
            self._py_step += n_steps
            pending_steps += n_steps
            samples += n_steps * batch_size
            if pending_steps >= summary_interval:
                flush_window()
            # iteration-granular checkpointing, e.g. SeveralIteration(n)
            # (ref Topology.scala checkpointTrigger evaluated per iteration).
            # With steps_per_loop > 1 every intermediate step is tested so
            # SeveralIteration(n) keeps its cadence (at most one snapshot
            # per loop; it reflects the loop-end state).
            if checkpoint_trigger and self.model_dir:
                last = losses[-1] if losses else None
                if any(checkpoint_trigger(self._epoch, s, last)
                       for s in range(start + 1, self._py_step + 1)):
                    flush_window()
                    self._save_snapshot()

        # each loop is timed on the host as data-wait (the next() on the
        # device iterator), dispatch (the async jitted call) and callback
        # (summary flush / checkpoint triggers); nothing waits for the
        # device between two flushes
        scan = steps_per_loop > 1
        if scan:
            it = iter(ds.device_scan_iterator(
                mesh, self.strategy, batch_size, steps_per_loop,
                shuffle=shuffle, seed=self.seed, epoch=self._epoch))
        else:
            it = iter(ds.device_iterator(mesh, self.strategy, batch_size,
                                         shuffle=shuffle, seed=self.seed,
                                         epoch=self._epoch,
                                         drop_remainder=True))

        def next_batch():
            with telemetry.annotation("data_wait"):
                return next(it, None)

        t0 = time.perf_counter()
        # the epoch's first batch pays for the shuffle and the staging
        with step_prof.phase("epoch/first_batch", "first_batch"):
            batch = next_batch()
        while batch is not None:
            t1 = time.perf_counter()
            x, y, k = batch
            n_steps = k if scan else 1
            # fault-injection step seam: one arrival per compiled train
            # dispatch (a fused scan counts once)
            resilience.maybe_fault("step")
            with telemetry.annotation("dispatch"):
                if scan:
                    self._state, loop_losses = self._train_scan(self._state,
                                                                (x, y))
                else:
                    self._state, logs = self._train_step(self._state, x, y)
                    loop_losses = logs["loss"]
                    if "counters" in logs:
                        pending_counters.append(logs["counters"])
            t2 = time.perf_counter()
            pending.append(loop_losses)
            after_steps(n_steps)
            step_prof.observe_step(self._py_step, t0, t1 - t0, t2 - t1,
                                   time.perf_counter() - t2)
            if profile_window is not None:
                profile_window.on_step(self._py_step)
            t0 = time.perf_counter()
            batch = next_batch()
        flush_window()
        dt = time.perf_counter() - t_epoch
        logger.info("epoch %d: %d samples in %.2fs (%.0f samples/s)",
                    self._epoch, samples, dt, samples / max(dt, 1e-9))
        return float(np.mean(losses)) if losses else float("nan")

    def evaluate(self, data, batch_size: int = 32,
                 feature_cols=None, label_cols=None) -> Dict[str, float]:
        """(ref orca/learn/tf/estimator.py evaluate:656)"""
        import jax
        ds = self._coerce(to_sharded_dataset(data, feature_cols, label_cols))
        mesh = self._ensure_mesh()
        self._init_state()
        self._build_eval_step()
        metric_states = [m.init_state() for m in self.metrics]
        loss_sum = 0.0
        count = 0.0
        for x, y, mask in ds.device_iterator(mesh, self.strategy, batch_size,
                                             drop_remainder=False):
            if mask is None:
                metric_states, ls, c = self._eval_step(
                    self._state, metric_states, x, y)
            else:
                metric_states, ls, c = self._eval_step_masked(
                    self._state, metric_states, x, y, mask)
            loss_sum += float(ls)
            count += float(c)
        out = {"loss": loss_sum / max(count, 1.0)}
        for m, ms in zip(self.metrics, metric_states):
            out[m.name] = m.result(ms)
        return out

    def predict(self, data, batch_size: int = 32, feature_cols=None,
                pipeline_window: int = 2) -> "np.ndarray | XShards":
        """(ref estimator.py predict:598-654; returns XShards when given
        XShards, ndarray otherwise)

        Batches flow through a bounded in-flight dispatch window
        (common/pipeline_io.py): up to ``pipeline_window`` dispatched
        batches stay on the device while the iterator stages the next
        host→device transfer, and ``device_get`` runs only when the window
        retires a batch — never inline with a dispatch. Outputs are
        bit-identical to the synchronous path (``pipeline_window=1`` is
        the synchronous cadence)."""
        import jax
        from analytics_zoo_tpu.common.pipeline_io import DevicePipeline
        was_shards = isinstance(data, XShards)
        if isinstance(data, tuple):
            # predict takes features only — a tuple is a multi-input x, not
            # an (x, y) pair
            data = {"x": data}
        ds = self._coerce(to_sharded_dataset(data, feature_cols, None))
        if ds.n == 0:
            raise ValueError("predict called on an empty dataset")
        mesh = self._ensure_mesh()
        self._init_state()
        self._build_predict()
        outs = []

        def take(comp):
            if comp.error is not None:
                raise comp.error
            preds, mask = comp.result, comp.ctx
            if mask is not None:
                valid = int(np.asarray(mask).sum())
                preds = jax.tree_util.tree_map(lambda a: a[:valid], preds)
            outs.append(preds)

        pipe = DevicePipeline(lambda x: self._predict_fn(self._state, x),
                              window=max(1, int(pipeline_window)),
                              trace_id="estimator_predict")
        with pipe:
            for x, _, mask in ds.device_iterator(
                    mesh, self.strategy, batch_size, drop_remainder=False):
                for comp in pipe.submit(x, ctx=mask):
                    take(comp)
            for comp in pipe.drain():
                take(comp)
        leaves = [jax.tree_util.tree_leaves(o) for o in outs]
        treedef = jax.tree_util.tree_structure(outs[0])
        merged = jax.tree_util.tree_unflatten(
            treedef,
            [np.concatenate([l[i] for l in leaves]) for i in range(len(leaves[0]))])
        if was_shards:
            return HostXShards([{"prediction": merged}])
        return merged

    # ------------- persistence -------------------------------------------
    def _save_snapshot(self):
        path = ckpt_lib.save_checkpoint(self.model_dir, self._state,
                                        self._py_step, self._epoch)
        logger.info("checkpoint saved: %s", path)
        return path

    def save(self, path: str):
        """Save weights + optimizer state (ref spark_estimator.save)."""
        os.makedirs(path, exist_ok=True)
        self._init_state()
        ckpt_lib.save_checkpoint(path, self._state, self._py_step,
                                 self._epoch, max_to_keep=10 ** 9)
        return path

    def load(self, path: str):
        found = ckpt_lib.find_latest_checkpoint(path)
        target = path if found is None else found[0]
        return self.load_orca_checkpoint(target)

    def load_orca_checkpoint(self, path: str, version: Optional[int] = None):
        """(ref orca/learn/tf/estimator.py:270-289)"""
        import jax
        if version is not None:
            path = os.path.join(path, f"ckpt-{version}")
        self._init_state()
        host_state = jax.device_get(self._state)
        state, meta = ckpt_lib.load_checkpoint(path, host_state)
        self._state = jax.device_put(state, self._state_sharding_tree)
        self._epoch = int(meta.get("epoch", 0))
        self._py_step = int(meta.get("iteration", 0))
        return self

    def _auto_resume_reload(self) -> Optional[str]:
        """Reload the newest checkpoint that validates against the live
        state tree (``fit(auto_resume=True)``'s retry boundary). Restores
        step/epoch counters for metric continuity; returns the restored
        path, or None when no version in ``model_dir`` is usable."""
        import jax
        self._init_state()
        host_state = jax.device_get(self._state)
        loaded = ckpt_lib.load_latest_checkpoint(self.model_dir, host_state)
        if loaded is None:
            return None
        state, meta, path = loaded
        self._state = jax.device_put(state, self._state_sharding_tree)
        self._epoch = int(meta.get("epoch", 0))
        self._py_step = int(meta.get("iteration", 0))
        return path

    def get_model(self):
        """Current host-side params pytree (ref spark_estimator.get_model)."""
        import jax
        self._init_state()
        return jax.device_get(self._state["params"])


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _get_by_path(tree, path_str: str):
    cur = tree
    for part in path_str.split("/"):
        if isinstance(cur, dict):
            cur = cur[part]
        else:
            cur = getattr(cur, part, None)
            if cur is None:
                return None
    return cur
