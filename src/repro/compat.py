"""Platform probe.

The kernels and models are written against the installed JAX (0.9): they
use ``jax.shard_map``, ``pltpu.CompilerParams``, ``jax.lax.ragged_dot``
and ``jax.lax.ragged_dot_general`` directly.  What remains here is the
one question the dispatch registry asks of the platform, kept as a plain
function so tests can monkeypatch it to exercise every dispatch branch on
any box.
"""
from __future__ import annotations

import jax


def has_tpu() -> bool:
    """True iff the default JAX backend is a real TPU (compiled Pallas)."""
    return jax.default_backend() == "tpu"
