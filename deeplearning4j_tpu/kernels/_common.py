"""Shared pallas-kernel plumbing: the Pallas-TPU import and backend
detection — one copy for every kernel module."""

from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu  # noqa: F401


def interpret_default() -> bool:
    return jax.default_backend() != "tpu"
