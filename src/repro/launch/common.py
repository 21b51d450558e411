"""What the training and serving launchers share: the model arguments
(architecture and depth cut) and the persistent compile cache."""
from __future__ import annotations

import dataclasses
import os

import jax

from repro.configs import ALL_ARCHS
from repro.models import get_config

REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))


def use_compile_cache():
    """Turn on JAX's persistent compile cache for a chip run.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and wins.
    Otherwise, on the TPU backend only, the cache lives at the fixed
    ``<repo>/.jax_cache`` (the path is part of the cache key, so it must
    not move between runs).  CPU runs, tests included, cache nothing.
    Call before the first compile.  Returns the directory in use, or None.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() != "tpu":
        return None
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def add_model_args(ap) -> None:
    ap.add_argument("--arch", default="granite-3-8b", choices=ALL_ARCHS)
    ap.add_argument("--tiny", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the depth to this many layers; every width "
                         "stays the published one")


def model_config(args):
    cfg = get_config(args.arch, tiny=args.tiny)
    if args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    return cfg
