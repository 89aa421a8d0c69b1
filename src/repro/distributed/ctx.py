"""Activation-sharding hint context (§Perf iteration A2).

``with_sharding_constraint`` is the only reliable way to pin GSPMD's
propagation through loop/reshape boundaries — critically, the constraint
also transposes onto the BACKWARD cotangents, which is where the chunked
attention lost its batch sharding (replicated f32[global_batch, ...] temps
in ``transpose(jvp())``).

Model code calls ``constrain(x, "dp", None, ..., "tp")`` with LOGICAL axis
names; the step builders install the active rules here.  Outside a rules
context (unit tests, single-device runs) it is a no-op.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Optional

import jax

from repro.distributed.sharding import ShardingRules, logical_to_physical

_RULES: contextvars.ContextVar[Optional[tuple]] = \
    contextvars.ContextVar("sharding_rules", default=None)

__all__ = ["activation_rules", "constrain"]


@contextlib.contextmanager
def activation_rules(rules: Optional[ShardingRules], mesh=None):
    """Install ``rules`` for :func:`constrain`; with ``mesh`` the hints are
    ``NamedSharding``s on it, otherwise they resolve against the mesh of
    the enclosing context."""
    tok = _RULES.set(None if rules is None else (rules, mesh))
    try:
        yield
    finally:
        _RULES.reset(tok)


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    active = _RULES.get()
    if active is None:
        return x
    rules, mesh = active
    spec = logical_to_physical(logical, rules)
    if mesh is not None:
        spec = jax.sharding.NamedSharding(mesh, spec)
    return jax.lax.with_sharding_constraint(x, spec)
