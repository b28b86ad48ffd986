"""Shared example boilerplate.

Examples run on whatever device JAX finds (`JAX_PLATFORMS=cpu` in the
environment selects the CPU, where the multi-device examples get 8
virtual devices), and provides the --smoke flag every example supports
(tiny sizes, a few seconds on CPU — the mode CI runs)."""

import argparse
import os
import pathlib
import sys

# the repo root (works without pip-installing the package)
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def setup(description: str):
    # only the host platform reads this flag; it is inert on an accelerator
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    ap = argparse.ArgumentParser(description=description)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for a fast functional check")
    return ap.parse_args()
