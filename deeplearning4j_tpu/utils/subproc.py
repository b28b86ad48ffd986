"""Child processes on an n-device virtual CPU platform.

A process that has initialized a JAX backend cannot be retargeted, and one
that holds a chip has only the chip's devices. Anything that needs an
n-device virtual CPU platform (multichip dry-runs, the dp-overhead bench
row) therefore runs in a child whose environment selects the CPU
(``JAX_PLATFORMS=cpu`` plus the host-device-count flag) before JAX
initializes. This module is the single copy of that recipe (used by
__graft_entry__ and bench.py).
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

COUNT_FLAG = "xla_force_host_platform_device_count"


def cpu_forced_env(n_devices: int,
                   base_env: Optional[Dict[str, str]] = None
                   ) -> Tuple[Dict[str, str], str]:
    """(env, preamble) for a child python that must see `n_devices` CPU
    devices. `preamble` is python source to exec FIRST in the child: it
    puts the repo root on sys.path (`-c` children don't get the '' entry
    under PYTHONSAFEPATH)."""
    env = dict(os.environ if base_env is None else base_env)
    env["JAX_PLATFORMS"] = "cpu"
    kept = [f for f in env.get("XLA_FLAGS", "").split() if COUNT_FLAG not in f]
    env["XLA_FLAGS"] = " ".join(kept + [f"--{COUNT_FLAG}={n_devices}"])
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    preamble = f"import sys; sys.path.insert(0, {repo!r});\n"
    return env, preamble


def env_forces_cpu(n_devices: int) -> bool:
    """True if THIS process's env already selects >= n_devices CPU devices
    (the child of :func:`cpu_forced_env`, or a pytest run)."""
    import re
    m = re.search(rf"{COUNT_FLAG}=(\d+)", os.environ.get("XLA_FLAGS", ""))
    return (os.environ.get("JAX_PLATFORMS") == "cpu" and m is not None
            and int(m.group(1)) >= n_devices)
