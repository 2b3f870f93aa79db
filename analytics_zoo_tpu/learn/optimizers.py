"""Optimizers + LR schedules (ref ``pyzoo/zoo/orca/learn/optimizers_impl.py``
327 LoC: SGD/Adam/AdamWeightDecay/LBFGS/... and ``schedule.py`` 218 LoC).

The reference lowers these to BigDL ``OptimMethod`` objects updated
per-partition on the JVM after the allreduce; here each wrapper builds an
``optax`` gradient transformation that runs sharded on-device inside the
jitted train step (optimizer state inherits the parameter sharding, so FSDP
shards it for free).
"""

from __future__ import annotations

from typing import Mapping, Optional, Union

import optax

Schedule = Union[float, "LRSchedule"]


# ---------------- schedules (ref orca/learn/schedule.py) ----------------

class LRSchedule:
    def to_optax(self, base_lr: float):
        raise NotImplementedError


class Default(LRSchedule):
    def to_optax(self, base_lr):
        return base_lr


class Poly(LRSchedule):
    """(ref schedule.py Poly: lr * (1 - iter/max)^power)"""

    def __init__(self, power: float, max_iteration: int):
        self.power, self.max_iteration = power, max_iteration

    def to_optax(self, base_lr):
        return optax.polynomial_schedule(
            init_value=base_lr, end_value=0.0, power=self.power,
            transition_steps=self.max_iteration)


class Exponential(LRSchedule):
    def __init__(self, decay_step: int, decay_rate: float, stair_case: bool = False):
        self.decay_step, self.decay_rate, self.stair_case = decay_step, decay_rate, stair_case

    def to_optax(self, base_lr):
        return optax.exponential_decay(
            base_lr, transition_steps=self.decay_step,
            decay_rate=self.decay_rate, staircase=self.stair_case)


class Step(LRSchedule):
    def __init__(self, step_size: int, gamma: float):
        self.step_size, self.gamma = step_size, gamma

    def to_optax(self, base_lr):
        return optax.exponential_decay(
            base_lr, transition_steps=self.step_size,
            decay_rate=self.gamma, staircase=True)


class Warmup(LRSchedule):
    """Linear warmup then constant (ref schedule.py Warmup delta)."""

    def __init__(self, warmup_steps: int):
        self.warmup_steps = warmup_steps

    def to_optax(self, base_lr):
        return optax.linear_schedule(0.0, base_lr, self.warmup_steps)


class WarmupCosine(LRSchedule):
    def __init__(self, warmup_steps: int, total_steps: int, end_value: float = 0.0):
        self.warmup_steps, self.total_steps, self.end_value = warmup_steps, total_steps, end_value

    def to_optax(self, base_lr):
        return optax.warmup_cosine_decay_schedule(
            0.0, base_lr, self.warmup_steps, self.total_steps, self.end_value)


def _lr(learning_rate, schedule: Optional[LRSchedule]):
    if schedule is None or isinstance(schedule, Default):
        return learning_rate
    return schedule.to_optax(learning_rate)


# ---------------- optimizers (ref orca/learn/optimizers_impl.py) --------

class Optimizer:
    def to_optax(self) -> optax.GradientTransformation:
        raise NotImplementedError

    @staticmethod
    def get(opt) -> "Optimizer":
        if isinstance(opt, Optimizer):
            return opt
        if isinstance(opt, optax.GradientTransformation):
            return _Raw(opt)
        if isinstance(opt, str):
            opt = {"name": opt}
        if isinstance(opt, Mapping):
            # {"name": "adam", "learningrate": 1e-5, ...}: what a
            # configuration file can say; the rest are the class's own
            # constructor arguments
            args = dict(opt)
            name = str(args.pop("name", "")).lower()
            table = {"sgd": SGD, "adam": Adam, "adamw": AdamWeightDecay,
                     "rmsprop": RMSprop, "adagrad": Adagrad,
                     "adadelta": Adadelta, "adamax": Adamax, "nadam": Nadam,
                     "lars": LARS, "lamb": LAMB, "lbfgs": LBFGS}
            if name not in table:
                raise ValueError(f"unknown optimizer {opt!r}")
            try:
                return table[name](**args)
            except TypeError as e:
                raise ValueError(f"optimizer {name!r}: {e}") from None
        raise TypeError(f"cannot build optimizer from {type(opt)}")


class _Raw(Optimizer):
    def __init__(self, tx):
        self.tx = tx

    def to_optax(self):
        return self.tx


class SGD(Optimizer):
    """(ref optimizers_impl.py SGD: momentum/dampening/nesterov/wd + schedule)"""

    def __init__(self, learningrate: float = 1e-3, momentum: float = 0.0,
                 nesterov: bool = False, weightdecay: float = 0.0,
                 leaningrate_schedule: Optional[LRSchedule] = None):
        self.lr, self.momentum, self.nesterov = learningrate, momentum, nesterov
        self.weightdecay, self.schedule = weightdecay, leaningrate_schedule

    def to_optax(self):
        parts = []
        if self.weightdecay:
            parts.append(optax.add_decayed_weights(self.weightdecay))
        parts.append(optax.sgd(_lr(self.lr, self.schedule),
                               momentum=self.momentum or None,
                               nesterov=self.nesterov))
        return optax.chain(*parts)


class Adam(Optimizer):
    def __init__(self, learningrate: float = 1e-3, beta1: float = 0.9,
                 beta2: float = 0.999, epsilon: float = 1e-8,
                 leaningrate_schedule: Optional[LRSchedule] = None):
        self.lr, self.b1, self.b2, self.eps = learningrate, beta1, beta2, epsilon
        self.schedule = leaningrate_schedule

    def to_optax(self):
        return optax.adam(_lr(self.lr, self.schedule), b1=self.b1, b2=self.b2,
                          eps=self.eps)


class AdamWeightDecay(Optimizer):
    """(ref optimizers_impl.py AdamWeightDecay — the BERT optimizer)"""

    def __init__(self, learningrate: float = 1e-3, weight_decay: float = 0.01,
                 beta1: float = 0.9, beta2: float = 0.999, epsilon: float = 1e-6,
                 total: int = -1, warmup_portion: float = -1.0):
        self.lr, self.wd = learningrate, weight_decay
        self.b1, self.b2, self.eps = beta1, beta2, epsilon
        self.total, self.warmup_portion = total, warmup_portion

    def to_optax(self):
        lr = self.lr
        if self.total > 0 and self.warmup_portion > 0:
            lr = optax.warmup_cosine_decay_schedule(
                0.0, self.lr, int(self.total * self.warmup_portion), self.total)
        return optax.adamw(lr, b1=self.b1, b2=self.b2, eps=self.eps,
                           weight_decay=self.wd)


class RMSprop(Optimizer):
    def __init__(self, learningrate: float = 1e-2, decayrate: float = 0.9,
                 epsilon: float = 1e-8):
        self.lr, self.decay, self.eps = learningrate, decayrate, epsilon

    def to_optax(self):
        return optax.rmsprop(self.lr, decay=self.decay, eps=self.eps)


class Adagrad(Optimizer):
    def __init__(self, learningrate: float = 1e-2):
        self.lr = learningrate

    def to_optax(self):
        return optax.adagrad(self.lr)


class Adadelta(Optimizer):
    def __init__(self, learningrate: float = 1.0, decayrate: float = 0.9,
                 epsilon: float = 1e-6):
        self.lr, self.rho, self.eps = learningrate, decayrate, epsilon

    def to_optax(self):
        return optax.adadelta(self.lr, rho=self.rho, eps=self.eps)


class Adamax(Optimizer):
    def __init__(self, learningrate: float = 2e-3, beta1: float = 0.9,
                 beta2: float = 0.999):
        self.lr, self.b1, self.b2 = learningrate, beta1, beta2

    def to_optax(self):
        return optax.adamax(self.lr, b1=self.b1, b2=self.b2)


class Nadam(Optimizer):
    def __init__(self, learningrate: float = 2e-3):
        self.lr = learningrate

    def to_optax(self):
        return optax.nadam(self.lr)


class LARS(Optimizer):
    """Layer-wise adaptive rate scaling — large-batch TPU training."""

    def __init__(self, learningrate: float = 1e-1, momentum: float = 0.9,
                 weight_decay: float = 1e-4):
        self.lr, self.momentum, self.wd = learningrate, momentum, weight_decay

    def to_optax(self):
        return optax.lars(self.lr, weight_decay=self.wd, momentum=self.momentum)


class LAMB(Optimizer):
    def __init__(self, learningrate: float = 1e-3, weight_decay: float = 0.0):
        self.lr, self.wd = learningrate, weight_decay

    def to_optax(self):
        return optax.lamb(self.lr, weight_decay=self.wd)


class LBFGS(Optimizer):
    """Memory-limited BFGS (ref optimizers_impl.py:99 LBFGS, BigDL's
    torch-style implementation). The reference's default path — no line
    search, fixed ``learningrate``-scaled steps along the two-loop
    direction — is exactly ``optax.lbfgs(linesearch=None)``, and that is
    what runs inside the jitted train step here. ``ncorrection`` is the
    history length. The reference's driver-loop knobs (``max_iter``,
    ``max_eval``, ``tolfun``, ``tolx``) govern BigDL's inner convergence
    loop, which has no analog in a per-minibatch SPMD step; they are
    accepted for signature parity and ignored."""

    def __init__(self, max_iter: int = 20, max_eval=None,
                 tolfun: float = 1e-5, tolx: float = 1e-9,
                 ncorrection: int = 100, learningrate: float = 1.0,
                 verbose: bool = False, linesearch=None,
                 linesearch_options=None):
        if linesearch is not None:
            raise ValueError("custom line-search functions are not "
                             "supported inside the jitted step; use the "
                             "default fixed-step mode")
        self.lr = learningrate
        self.ncorrection = int(ncorrection)

    def to_optax(self):
        return optax.lbfgs(self.lr, memory_size=self.ncorrection,
                           linesearch=None)
