"""Leaf checksums for the state scrubber.

Device leaves are reduced on device — their 32-bit storage words summed
mod 2^32 with odd position weights (no device->host transfer of the data;
a single flipped bit changes exactly one word by ±2^k, hence the sum by
±2^k*(2j+1) — an odd multiple of 2^k that can never cancel mod 2^32 — so
any single-bit upset is caught).  The word view and the weights come from
``repro/kernels/block_hash`` — the hash that detects dirty blocks for
incremental checkpoints: a leaf checksum is the mod-2^32 sum of its block
hashes, so scrub and delta share the hash value.  The scrub takes it in one
pass over each leaf in its own shape (``checksum_words``), and one program
returns the checksums of all the device leaves it is given as one array.
Host leaves reuse the zero-copy ``crc32_array`` from core/io_engine.py.
Either way a leaf's checksum is a plain int, stable across recomputation
on identical bytes.

``launch`` splits a batch in two, so that the scrubber can time the
dispatch (``sdc.reduce``) apart from the wait for its result; it counts
the bytes of the device leaves it hands to the reduction into the
``sdc.checksummed_bytes`` counter of the ``Observability`` it is given.
"""
from __future__ import annotations

from typing import Any, Callable, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.block_hash.ops import checksum_words
from repro.obs.metrics import span


@jax.jit
def _device_sums(leaves):
    """(k,) uint32: the checksums of k device leaves, one array so that
    the host reads them in one transfer."""
    return jnp.stack([checksum_words(x) for x in leaves])


def _host_crc(leaf) -> int:
    # deferred: repro.core.__init__ imports repro.sdc (the facade wires the
    # scrubber in), so a module-level import here would be circular
    from repro.core.io_engine import crc32_array

    return crc32_array(np.ascontiguousarray(leaf))


def leaf_checksum(leaf: Any) -> int:
    """Checksum one pytree leaf; device arrays reduce on device."""
    if isinstance(leaf, jax.Array):
        return int(jax.device_get(_device_sums([leaf]))[0])
    return _host_crc(np.asarray(leaf))


def launch(leaves: List[Any], obs=None) -> Callable[[], List[int]]:
    """Dispatch the checksums of many leaves: ONE jitted device reduction
    for all device leaves (per-leaf dispatch would dominate the scrub cost
    on small states).  Returns a function that waits for it (one
    device_get of one array) and adds host crc32 for the rest, in
    ``leaves`` order."""
    dev = [v for v in leaves if isinstance(v, jax.Array)]
    sums = None
    if dev:
        with span(obs, "sdc.reduce"):
            sums = _device_sums(dev)
        if obs is not None:
            obs.registry.counter("sdc.checksummed_bytes").inc(
                sum(v.size * v.dtype.itemsize for v in dev))

    def fetch() -> List[int]:
        got = iter(jax.device_get(sums).tolist() if dev else ())
        return [next(got) if isinstance(v, jax.Array)
                else _host_crc(np.asarray(v)) for v in leaves]
    return fetch


def checksums(leaves: List[Any]) -> List[int]:
    """Checksum many leaves: ``launch`` them and wait."""
    return launch(leaves)()


def named_leaves(tree) -> List[Tuple[str, Any]]:
    """(dotted-name, leaf) pairs — THE checkpoint-manifest naming, so a
    scrubber hit, a bit-flip schedule, and a checkpoint leaf all refer to
    the same thing (delegates to the manifest's own flattener; import
    deferred for the same core<->sdc circularity as _host_crc)."""
    from repro.core.checkpoint import _flatten_named

    return _flatten_named(tree)
