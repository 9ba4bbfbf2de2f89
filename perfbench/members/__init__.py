"""Fleet members: one module per model family, named by a configuration's
``"member"`` key and imported as ``perfbench.members.<member>``.

A member module provides

``program(cfg) -> (init_fn, loss_fn)``
    the program's own init and loss for the configuration ``cfg`` (the
    configuration file's JSON object), passed unchanged to
    ``run_protocol_training``: ``init_fn(key) -> params``,
    ``loss_fn(params, batch) -> scalar``;
``reference_init(cfg, key) -> params``
    plain JAX, float32, written from the published description and not
    from the program; the same tree as the program's params and, from the
    same key, the same weights;
``reference_loss(cfg, params, batch, *, half_batch=False) -> scalar``
    plain JAX, float32 where the params are float32; ``batch`` is the
    data generator's dict, passed through whole. ``half_batch`` is a
    planted fault: the mean over the first half of the batch only;
``flops_per_sample(cfg) -> int``
    the operations one learner's SGD step requires per sample (one draw
    of the stream), which ``metrics/step_mfu.py`` divides;
``LEARNER_BLOCK``
    how many learners the reference updates at once.
"""
from __future__ import annotations

import importlib
from types import ModuleType

REQUIRED = ("program", "reference_init", "reference_loss",
            "flops_per_sample", "LEARNER_BLOCK")


def load(cfg: dict, config_name: str) -> ModuleType:
    """The member module that configuration ``config_name`` names, with
    every part a member provides; anything missing is refused here, with
    the configuration and the member in the message."""
    kind = cfg.get("member")
    if not isinstance(kind, str) or not kind.isidentifier():
        raise ValueError(f"configuration {config_name!r} names no member "
                         f"module (its \"member\" key reads {kind!r})")
    try:
        module = importlib.import_module(f"perfbench.members.{kind}")
    except ModuleNotFoundError as e:
        if e.name != f"perfbench.members.{kind}":
            raise
        raise ValueError(f"configuration {config_name!r} names member "
                         f"{kind!r}, but there is no perfbench/members/"
                         f"{kind}.py") from None
    missing = [k for k in REQUIRED if not hasattr(module, k)]
    if missing:
        raise ValueError(f"configuration {config_name!r} names member "
                         f"{kind!r}, whose module lacks {missing}")
    return module
