"""Sharded, atomic, resharding-on-restore checkpoint manager (from scratch).

DeLIA mapping (DESIGN.md S2):
- *global state*  = any pytree (TrainState): each host writes only its
  addressable shards + metadata; restore can target ANY mesh/sharding
  (elastic recovery) because the manifest records global shapes and every
  shard's index span.
- *local state*   = small JSON dict per host (data-pipeline cursor etc.).

Layout (one directory per step):

    <dir>/step_00000420/
        manifest.json               global shapes/dtypes/codec/CRCs
        <leaf-name>.s<k>.npy        shard k of that leaf (np .npy payload)
        local_h<i>.json             per-host local state
        ack_h<i>                    per-host completion marker
    <dir>/step_00000420.tmp.<pid>   staging dir, atomically renamed

Commit protocol: every host writes shards + ack into the staging dir; host 0
renames it into place once all acks are present (single-process runs commit
immediately).  A reader only trusts directories whose manifest parses and
whose CRCs verify — a crash mid-write never corrupts the latest checkpoint.
Staging directories abandoned by crashed writers are swept on manager init
and at every GC (a dir is stale when no live process owns its pid suffix
and no writer of THIS process has it registered in-flight).

Fast path (the Young/Daly C term, end to end):

1. *Snapshot* (the only on-critical-path cost in async mode): with
   ``device_codec=True`` each floating leaf >= 1 KiB is quantized to int8 +
   per-block fp32 scales *on device* (Pallas kernel on TPU, jnp twin
   elsewhere — see core/codec.DeviceCodec) and the int8 payload is what
   crosses the device->host link: ~3.9x fewer bytes than fp32.  All shards
   transfer in one batched ``jax.device_get``.
2. *Write*: shards are encoded (host codec, if any) and written
   concurrently by a ``ShardIOEngine`` thread pool; each ``.npy`` is
   streamed through memoryview chunks with the CRC32 computed in the same
   pass — no ``tobytes()`` copies anywhere.
3. *Durability*: fsync is batched — files first, then one directory fsync —
   instead of a per-file write->fsync lockstep (``fsync`` mode knob).
4. *Restore*: shard loads and leaf assembly are parallelized on the same
   pool; CRC verification is zero-copy over the loaded buffers; shard spans
   are validated to EXACTLY tile each leaf (a lost host manifest raises
   IOError instead of returning uninitialized memory, so ``restore_latest``
   walks back).

Incremental ("delta") mode (``delta=True``, docs/checkpointing.md):

Each shard is split into fixed-size blocks of ``delta_block`` elements
whose mod-2^32 word-sum hashes are computed ON DEVICE by the block_hash
Pallas kernel (the same reduction the SDC scrubber uses for leaf
checksums).  A save writes only the blocks whose hash changed since the
last committed checkpoint: clean blocks become manifest references into
the parent step's files, forming a bounded-depth chain (``full_every``
forces a periodic full save; a restore resets the base, so the save after
a rollback is always full).  ``delta_block`` must be a multiple of the
int8 codec's 256-element block so a standalone encode of the dirty blocks
is bit-identical to the matching slice of a full-save encode — delta
restores are therefore bit-exact against a full-save oracle for every
codec config.  ``_gc`` is chain-aware: a parent step survives ``keep``
while any retained child references it; a corrupt parent invalidates every
child that references it (the chain walk raises IOError and
``restore_latest`` skips the whole chain).

Async mode: ``save(..., blocking=False)`` snapshots device arrays to host
memory and hands serialization to a writer thread (double-buffered: a new
save drains the previous one; ``wait()`` re-raises writer errors).
"""
from __future__ import annotations

import functools
import json
import os
import re
import shutil
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.codec import (CODECS, Codec, DeviceCodec,
                              validate_delta_block)
from repro.core.io_engine import (ShardIOEngine, crc32_array, fsync_path,
                                  pid_alive, read_json, write_json,
                                  write_npy)
from repro.kernels.block_hash.ops import batched_block_hashes
from repro.kernels.block_hash.ref import block_hashes_np
from repro.obs.metrics import span

_STEP_RE = re.compile(r"^step_(\d{8})$")
_STAGING_RE = re.compile(r"^step_(\d{8})\.tmp\.(\d+)$")
_LOCAL_SHARD_RE = re.compile(r"^local_s(\d{5})\.json$")

# leaves below this many elements are always saved in full (same floor the
# codecs use: hashing/packing overhead would exceed the bytes saved)
_DELTA_MIN_ELEMS = 1024


def _leaf_name(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return ".".join(parts)


def _flatten_named(tree) -> List[Tuple[str, Any]]:
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [(_leaf_name(p), v) for p, v in leaves]


@functools.partial(jax.jit, static_argnames=("block",))
def _gather_blocks_device(x, idx, block: int):
    """Jitted device gather (an eager op chain pays ~10x in dispatch +
    unfused gather lowering).  Retraces per (shape, dirty-count) — the
    steady-state churn pattern is stable, so the cache hits."""
    flat = x.reshape(-1)
    pad = (-flat.size) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, block)[idx].reshape(-1)


def _gather_blocks(data, idx: np.ndarray, block: int):
    """Blocks ``idx`` of the flattened shard, concatenated flat (each block
    zero-padded to ``block`` elements).  Device arrays gather ON DEVICE so
    only the dirty bytes ever cross the device->host link."""
    if isinstance(data, jax.Array):
        return _gather_blocks_device(data, jnp.asarray(idx, jnp.int32),
                                     int(block))
    flat = np.ascontiguousarray(data).reshape(-1)
    pad = (-flat.size) % block
    if pad:
        flat = np.pad(flat, (0, pad))
    return np.ascontiguousarray(flat.reshape(-1, block)[idx].reshape(-1))


class SaveStats:
    def __init__(self, step, bytes_written, snapshot_s, write_s, blocking,
                 kind="full", dirty_blocks=0, total_blocks=0):
        self.step = step
        self.bytes_written = bytes_written
        self.snapshot_seconds = snapshot_s
        self.write_seconds = write_s
        self.blocking = blocking
        self.kind = kind                      # "full" | "delta"
        self.dirty_blocks = dirty_blocks      # blocks written (delta mode)
        self.total_blocks = total_blocks      # blocks tracked (delta mode)

    def __repr__(self):
        extra = ""
        if self.total_blocks:
            extra = (f", kind={self.kind}, blocks={self.dirty_blocks}/"
                     f"{self.total_blocks}")
        return (f"SaveStats(step={self.step}, MB={self.bytes_written/1e6:.1f},"
                f" snapshot={self.snapshot_seconds:.3f}s,"
                f" write={self.write_seconds:.3f}s, blocking={self.blocking}"
                f"{extra})")


class CheckpointManager:
    # staging dirs currently owned by a live writer of THIS process — the
    # stale-staging sweep must never remove these.  REFCOUNTED, not a set:
    # in single-process multi-host simulations several managers register
    # the SAME staging path (same pid, same step), and one manager's
    # close() must not strip protection while another's writer still uses
    # the dir.  A commit clears the path outright (the dir was renamed
    # away; every host's interest in it is moot).
    _ACTIVE_STAGING: Dict[str, int] = {}
    _STAGING_LOCK = threading.Lock()

    def __init__(self, directory: str, *, host_id: int = 0, num_hosts: int = 1,
                 codec: Optional[str] = None, device_codec: bool = False,
                 io_threads: int = 0, fsync: str = "batch",
                 verify_crc: bool = True, keep: int = 3,
                 delta: bool = False, delta_block: int = 65536,
                 full_every: int = 8):
        self.directory = directory
        self.host_id = host_id
        self.num_hosts = num_hosts
        if device_codec:
            if codec not in (None, "int8"):
                raise ValueError(
                    f"device_codec implies the int8 layout, got codec={codec!r}")
            codec = "int8"
        self.codec: Optional[Codec] = CODECS[codec] if codec else None
        self.codec_name = codec
        self._dcodec: Optional[DeviceCodec] = (DeviceCodec()
                                               if device_codec else None)
        self._engine = ShardIOEngine(threads=io_threads, fsync_mode=fsync)
        self.verify_crc = verify_crc
        self.keep = keep
        self.delta = bool(delta)
        self.delta_block = validate_delta_block(delta_block) if delta else int(
            delta_block)
        if delta and full_every < 1:
            raise ValueError(f"full_every must be >= 1, got {full_every}")
        self.full_every = int(full_every)
        # per-shard base of the last committed save: fname -> {step, hashes,
        # block_steps, step_sids, spans, dtype, size}.  In-memory only: a
        # restarted manager saves one full checkpoint first, then resumes
        # deltas.  ``step_sids`` maps each referenced step to the lineage id
        # its shards were saved under — a walk-back + resume can REGENERATE
        # a parent step number with different content, and a stale delta
        # must not silently resolve against it (restore verifies sids).
        self._delta_base: Dict[str, Dict[str, Any]] = {}
        self._chain_len = 0           # delta saves since the last full
        self._my_staging: Set[str] = set()   # this manager's registrations
        os.makedirs(directory, exist_ok=True)
        self._writer: Optional[threading.Thread] = None
        self._writer_err: Optional[BaseException] = None
        # repro.obs.Observability (Dependability.attach_obs): turns on the
        # ckpt.drain / ckpt.snapshot / ckpt.write / ckpt.commit spans
        self.obs = None
        self._sweep_stale_staging()

    # ------------------------------------------------------------------
    # save
    # ------------------------------------------------------------------
    def _staging(self, step: int) -> str:
        return os.path.join(self.directory,
                            f"step_{step:08d}.tmp.{os.getpid()}")

    def _final(self, step: int) -> str:
        return os.path.join(self.directory, f"step_{step:08d}")

    def _register_staging(self, path: str) -> None:
        active = CheckpointManager._ACTIVE_STAGING
        with CheckpointManager._STAGING_LOCK:
            active[path] = active.get(path, 0) + 1
        self._my_staging.add(path)

    def _unregister_staging(self, path: str) -> None:
        """Drop THIS manager's hold on ``path`` (other co-hosted managers'
        holds keep protecting it)."""
        if path not in self._my_staging:
            return
        self._my_staging.discard(path)
        active = CheckpointManager._ACTIVE_STAGING
        with CheckpointManager._STAGING_LOCK:
            count = active.get(path, 0)
            if count <= 1:
                active.pop(path, None)
            else:
                active[path] = count - 1

    def _clear_staging(self, path: str) -> None:
        """Commit path: the staging dir was renamed into place, so every
        host's registration of it is moot — clear outright."""
        self._my_staging.discard(path)
        with CheckpointManager._STAGING_LOCK:
            CheckpointManager._ACTIVE_STAGING.pop(path, None)

    def _sweep_stale_staging(self) -> None:
        """Remove ``step_<n>.tmp.<pid>`` staging dirs abandoned by crashed
        writers.  A dir is stale unless a writer of this process has it
        registered in-flight, or its pid suffix belongs to another LIVE
        process (a co-hosted writer mid-save)."""
        try:
            names = os.listdir(self.directory)
        except FileNotFoundError:
            return
        for dname in names:
            m = _STAGING_RE.match(dname)
            if not m:
                continue
            path = os.path.join(self.directory, dname)
            with CheckpointManager._STAGING_LOCK:
                if CheckpointManager._ACTIVE_STAGING.get(path, 0) > 0:
                    continue
            pid = int(m.group(2))
            if pid != os.getpid() and pid_alive(pid):
                continue
            shutil.rmtree(path, ignore_errors=True)

    def _shards_of(self, value):
        """Addressable shards of a jax.Array (kept on device) or a single
        numpy shard; (spans, data) pairs."""
        if isinstance(value, jax.Array) and hasattr(value, "addressable_shards"):
            out = []
            for sh in value.addressable_shards:
                idx = sh.index  # tuple of slices into the global array
                spans = [[s.start or 0,
                          s.stop if s.stop is not None else dim]
                         for s, dim in zip(idx, value.shape)] or []
                out.append((sh.replica_id, spans, sh.data))
            # only keep replica 0 to avoid duplicate writes
            return [(spans, data) for rid, spans, data in out if rid == 0]
        arr = np.asarray(value)
        spans = [[0, d] for d in arr.shape]
        return [(spans, arr)]

    def _dcodec_ok(self, data) -> bool:
        """Would a full save device-encode this shard?  Delta saves must
        encode gathered dirty blocks iff the full save would have (decided
        on the ORIGINAL shard — a gathered payload can be smaller than the
        codec floor), or the decoded values diverge from the full-save
        oracle."""
        return (self._dcodec is not None and isinstance(data, jax.Array)
                and jnp.issubdtype(data.dtype, jnp.floating)
                and data.size >= 1024)

    def _host_codec_ok(self, data) -> bool:
        """Same, for the host-side codec in the writer pool (applies to
        numpy shards even in device-codec mode, matching the full path)."""
        if self.codec is None:
            return False
        dt = np.dtype(data.dtype) if hasattr(data, "dtype") else None
        return dt in (np.float32, np.float64) and data.size >= 1024

    def _append_payload(self, item, smeta, payload, dev, fill,
                        dcodec_ok: bool, host_codec_ok: bool):
        """Route one shard payload (full data or gathered dirty blocks)
        into the write plan: device-encode, defer transfer, or keep host."""
        if dcodec_ok and isinstance(payload, jax.Array):
            q, s = self._dcodec.encode(payload)
            smeta["codec"] = {"name": self.codec_name,
                              **DeviceCodec.block_meta(payload.shape)}
            item["kind"] = "parts"
            item["parts"] = [None, None]
            for j, a in enumerate((q, s)):
                fill.append((item["parts"], j))
                dev.append(a)
        elif isinstance(payload, jax.Array):
            item["kind"] = "host"
            item["codec_ok"] = host_codec_ok
            item["data"] = None
            fill.append((item, "data"))
            dev.append(payload)
        else:
            item["kind"] = "host"
            item["codec_ok"] = host_codec_ok
            item["data"] = payload

    def _snapshot(self, named, step: int, kind: str, sid: str):
        """Device -> host: the only cost on the BSP critical path in async
        mode.  With device_codec, eligible leaves are quantized on device
        first so only int8 + scales cross the link; all device buffers move
        in one batched device_get.  In delta mode each shard's block hashes
        are computed first (on device, one batched transfer of the tiny
        hash vectors) and only dirty blocks are gathered + transferred.

        Returns (shard_plan, manifest_arrays, pending_base, dirty, total)
        where each plan item owns its manifest shard-meta dict (mutated by
        the writer jobs with codec/crc info before the manifest is dumped)
        and ``pending_base`` is the delta base to commit once the write
        lands on disk.
        """
        plan: List[Dict[str, Any]] = []
        manifest_arrays: Dict[str, Any] = {}
        rows: List[Dict[str, Any]] = []
        for name, value in named:
            shards = self._shards_of(value)
            first = shards[0][1]
            dtype = str(first.dtype if hasattr(first, "dtype")
                        else np.asarray(first).dtype)
            entry = {"shape": list(np.shape(value)), "dtype": dtype,
                     "shards": []}
            for k, (spans, data) in enumerate(shards):
                fname = f"{name}.s{self.host_id}_{k}.npy"
                smeta: Dict[str, Any] = {"file": fname, "spans": spans}
                if self.delta:
                    smeta["sid"] = sid       # lineage id delta children pin
                entry["shards"].append(smeta)
                row = {"fname": fname, "meta": smeta, "spans": spans,
                       "data": data, "dtype": dtype}
                if self.delta and data.size >= _DELTA_MIN_ELEMS:
                    if isinstance(data, jax.Array):
                        row["hash_me"] = True
                    else:
                        row["hashes"] = block_hashes_np(np.asarray(data),
                                                        self.delta_block)
                rows.append(row)
            manifest_arrays[name] = entry
        # ONE jitted dispatch hashes every device shard, ONE transfer moves
        # the (tiny) hash vectors
        pend = [r for r in rows if r.pop("hash_me", False)]
        if pend:
            hashes = batched_block_hashes([r["data"] for r in pend],
                                          self.delta_block)
            for r, h in zip(pend, jax.device_get(hashes)):
                r["hashes"] = np.asarray(h)

        pending_base: Dict[str, Dict[str, Any]] = {}
        dirty_total = blocks_total = 0
        dev: List[Any] = []          # device arrays awaiting transfer
        fill: List[Tuple[Any, Any]] = []  # (container, key) to fill per dev
        for row in rows:
            fname, smeta, data = row["fname"], row["meta"], row["data"]
            item: Dict[str, Any] = {"fname": fname, "meta": smeta}
            base = self._delta_base.get(fname)
            h = row.get("hashes")
            if h is not None:
                pending_base[fname] = {
                    "step": step, "hashes": h, "spans": row["spans"],
                    "dtype": row["dtype"], "size": int(data.size),
                    "block_steps": np.full(h.size, step, np.int64),
                    "step_sids": {step: sid}}
                blocks_total += h.size
            use_delta = (kind == "delta" and h is not None
                         and base is not None
                         and base["spans"] == row["spans"]
                         and base["dtype"] == row["dtype"]
                         and base["size"] == int(data.size))
            if use_delta:
                dirty = np.nonzero(h != base["hashes"])[0]
                if dirty.size == h.size:
                    use_delta = False       # fully dirty: plain full shard
            if not use_delta:
                if h is not None:
                    dirty_total += h.size
                self._append_payload(item, smeta, data, dev, fill,
                                     dcodec_ok=self._dcodec_ok(data),
                                     host_codec_ok=self._host_codec_ok(data))
                plan.append(item)
                continue
            dirty_total += int(dirty.size)
            block_steps = base["block_steps"].copy()
            block_steps[dirty] = step
            clean = np.nonzero(h == base["hashes"])[0]
            parents: Dict[int, List[int]] = {}
            for b in clean:
                parents.setdefault(int(base["block_steps"][b]),
                                   []).append(int(b))
            pending_base[fname]["block_steps"] = block_steps
            pending_base[fname]["step_sids"] = {
                step: sid, **{s: base["step_sids"][s] for s in parents}}
            smeta["delta"] = {
                "block": self.delta_block, "nblocks": int(h.size),
                "size": int(data.size),
                "local": [int(b) for b in dirty],
                "parents": {str(s): bs for s, bs in sorted(parents.items())},
                "parent_sids": {str(s): base["step_sids"][s]
                                for s in parents},
            }
            if dirty.size == 0:
                smeta["file"] = None     # nothing local: pure reference
                continue
            gathered = _gather_blocks(data, dirty, self.delta_block)
            self._append_payload(item, smeta, gathered, dev, fill,
                                 dcodec_ok=self._dcodec_ok(data),
                                 host_codec_ok=self._host_codec_ok(data))
            plan.append(item)
        if dev:
            for (container, key), arr in zip(fill, jax.device_get(dev)):
                container[key] = np.asarray(arr)
        return plan, manifest_arrays, pending_base, dirty_total, blocks_total

    def _write_shard(self, staging: str, item: Dict[str, Any]) -> Tuple[str, int]:
        """One writer-pool job: (host-)encode + stream one shard to disk."""
        path = os.path.join(staging, item["fname"])
        meta = item["meta"]
        per_file = self._engine.per_file_fsync
        if item["kind"] == "parts":     # device-encoded: q blocks + scales
            nbytes, crc = write_npy(path, item["parts"], fsync=per_file)
        else:
            payload = item["data"]
            if item.get("codec_ok"):
                payload, codec_meta = self.codec.encode(payload)
                meta["codec"] = {"name": self.codec_name, **codec_meta}
            nbytes, crc = write_npy(path, payload, fsync=per_file)
        meta["crc32"] = crc
        return path, nbytes

    def save(self, step: int, state, local_state: Optional[Dict] = None, *,
             local_shards: Optional[List[Dict]] = None,
             mesh_meta: Optional[Dict] = None,
             blocking: bool = True) -> SaveStats:
        """``local_state``: this host's local-scope dict (one file per host).
        ``local_shards``: finer-grained local scope — one dict per DP shard
        this host owns, each written as its OWN ``local_s<k>.json`` file so
        restore can remap them individually when the shard count changes
        (the feature the paper's FWI study could not enable).
        ``mesh_meta``: the mesh the state was sharded on when saved — e.g.
        ``{"dp": 2, "tp": 2, "ep": 2, "moe_ep": 2, "dead_experts": []}`` —
        recorded in the manifest so restore can rebuild expert placement
        (``reshard_state`` reads it back via ``manifest_meta``)."""
        self.wait()  # double-buffer: drain previous async write
        obs = self.obs
        t0 = time.perf_counter()
        with span(obs, "ckpt.snapshot"):
            kind = "full"
            if (self.delta and self._delta_base
                    and self._chain_len + 1 < self.full_every):
                kind = "delta"
            # fresh lineage id per save: a walk-back + resume can
            # regenerate a step NUMBER with different content; delta
            # children pin the id so restore refuses to mix generations
            sid = uuid.uuid4().hex[:16]
            named = _flatten_named(state)
            (shard_plan, manifest_arrays, pending_base, dirty,
             total) = self._snapshot(named, step, kind, sid)
        snapshot_s = time.perf_counter() - t0

        def write():
            t1 = time.perf_counter()
            staging = self._staging(step)
            self._register_staging(staging)
            try:
                with span(obs, "ckpt.write"):
                    os.makedirs(staging, exist_ok=True)
                    total_b, paths = self._engine.run_jobs(
                        [functools.partial(self._write_shard, staging, item)
                         for item in shard_plan])
                with span(obs, "ckpt.commit"):
                    self._commit(step, staging, paths, kind, manifest_arrays,
                                 local_state, local_shards, mesh_meta)
            except BaseException:
                self._unregister_staging(staging)
                raise
            # the write landed: commit the delta base (a failed write never
            # becomes a parent; hosts that don't commit the rename still
            # advance — their shards are on disk awaiting the commit)
            if self.delta:
                self._delta_base.update(pending_base)
                self._chain_len = 0 if kind == "full" else self._chain_len + 1
            return total_b, time.perf_counter() - t1

        if blocking:
            total_b, write_s = write()
            return SaveStats(step, total_b, snapshot_s, write_s, True,
                             kind=kind, dirty_blocks=dirty,
                             total_blocks=total)

        stats = SaveStats(step, 0, snapshot_s, 0.0, False, kind=kind,
                          dirty_blocks=dirty, total_blocks=total)

        def run():
            try:
                total_b, write_s = write()
                stats.bytes_written = total_b
                stats.write_seconds = write_s
            except BaseException as e:  # surfaced on next wait()
                self._writer_err = e

        self._writer = threading.Thread(target=run, daemon=True)
        self._writer.start()
        return stats

    def _commit(self, step: int, staging: str, paths: List[str], kind: str,
                manifest_arrays: Dict[str, Any], local_state: Optional[Dict],
                local_shards: Optional[List[Dict]],
                mesh_meta: Optional[Dict]) -> None:
        """Manifest, local state, ack and durable files in ``staging``;
        then, once every host acked, the rename into place."""
        manifest = {
            "step": step,
            "num_hosts": self.num_hosts,
            "codec": self.codec_name,
            "kind": kind,
            "arrays": manifest_arrays,
        }
        if mesh_meta is not None:
            manifest["mesh"] = dict(mesh_meta)
        if local_shards is not None:
            manifest["local_shards"] = [int(sd.get("shard", k))
                                        for k, sd in enumerate(local_shards)]
        mpath = os.path.join(staging, f"manifest_h{self.host_id}.json")
        paths.append(write_json(mpath, manifest))
        if local_state is not None:
            lpath = os.path.join(staging, f"local_h{self.host_id}.json")
            paths.append(write_json(lpath, local_state))
        for k, sd in enumerate(local_shards or ()):
            idx = int(sd.get("shard", k))
            spath = os.path.join(staging, f"local_s{idx:05d}.json")
            paths.append(write_json(spath, sd))
        apath = os.path.join(staging, f"ack_h{self.host_id}")
        open(apath, "w").close()
        paths.append(apath)
        self._engine.finalize(staging, paths)
        # commit when all hosts acked (single-process: immediately)
        acks = [os.path.exists(os.path.join(staging, f"ack_h{h}"))
                for h in range(self.num_hosts)]
        if all(acks) and self.host_id == 0:
            final = self._final(step)
            if os.path.exists(final):
                shutil.rmtree(final)
            os.rename(staging, final)
            self._clear_staging(staging)
            if self._engine.fsync_mode != "none":
                fsync_path(self.directory)  # make the rename durable
            self._gc()

    def wait(self) -> None:
        """Drain the async writer (``ckpt.drain``); re-raise its error."""
        if self._writer is not None:
            with span(self.obs, "ckpt.drain"):
                self._writer.join()
            self._writer = None
        if self._writer_err is not None:
            err, self._writer_err = self._writer_err, None
            raise err

    def close(self) -> None:
        """Drain the async writer and shut the I/O pool down.  Also drop
        this manager's staging registrations: a step that never committed
        (e.g. another host's ack never arrived) stays registered while the
        manager lives so co-hosted sweeps leave it alone, but must become
        sweepable once the manager is done with it."""
        self.wait()
        for path in list(self._my_staging):
            self._unregister_staging(path)
        self._engine.close()

    def _parent_steps(self, step: int) -> Set[int]:
        """Steps referenced by ``step``'s delta manifests (direct parents).
        Raises on an unreadable manifest — callers deciding what to DELETE
        must treat that conservatively, not as 'no parents'."""
        out: Set[int] = set()
        merged = self._load_manifests(step)
        for entry in merged.values():
            for sh in entry["shards"]:
                d = sh.get("delta")
                if d:
                    out.update(int(s) for s in d["parents"])
        return out

    def _gc(self) -> None:
        """Prune beyond ``keep`` — but chain-aware: a step survives while
        any retained delta checkpoint (transitively) references it.  If any
        retained manifest cannot be read (even transiently — EMFILE under
        a loaded I/O pool, say), SKIP deletion this round: deleting a
        parent that an unreadable child still references would destroy
        every retained delta, so the safe failure mode is keeping too
        much, never too little."""
        steps = self.all_steps()
        if self.keep:
            keep_set = set(steps[-self.keep:])
            frontier = list(keep_set)
            try:
                while frontier:
                    for p in self._parent_steps(frontier.pop()):
                        if p not in keep_set:
                            keep_set.add(p)
                            frontier.append(p)
            except (OSError, ValueError, json.JSONDecodeError):
                keep_set = None        # can't prove safety: delete nothing
            if keep_set is not None:
                for s in steps:
                    if s not in keep_set:
                        shutil.rmtree(self._final(s), ignore_errors=True)
        self._sweep_stale_staging()

    # ------------------------------------------------------------------
    # restore
    # ------------------------------------------------------------------
    def all_steps(self) -> List[int]:
        out = []
        for d in os.listdir(self.directory):
            m = _STEP_RE.match(d)
            if m and os.path.exists(os.path.join(self.directory, d,
                                                 "manifest_h0.json")):
                out.append(int(m.group(1)))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def manifest_meta(self, step: int) -> Optional[Dict[str, Any]]:
        """The ``mesh_meta`` dict recorded at ``save`` time (None when the
        step predates mesh metadata or does not exist).  This is how expert
        placement survives a restart: the manifest says which (dp, tp, ep)
        grid — and which dead experts — the checkpoint was written under."""
        if step is None:
            return None
        p = os.path.join(self._final(step), "manifest_h0.json")
        if not os.path.exists(p):
            return None
        with open(p) as f:
            return json.load(f).get("mesh")

    def _load_manifests(self, step: int) -> Dict[str, Any]:
        final = self._final(step)
        merged: Dict[str, Any] = {}
        for h in range(self.num_hosts):
            p = os.path.join(final, f"manifest_h{h}.json")
            if not os.path.exists(p):
                continue
            with open(p) as f:
                man = json.load(f)
            for name, entry in man["arrays"].items():
                if name not in merged:
                    merged[name] = {"shape": entry["shape"],
                                    "dtype": entry["dtype"], "shards": []}
                merged[name]["shards"].extend(entry["shards"])
        return merged

    def _check_tiling(self, name: str, shape: Tuple[int, ...],
                      shards: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
        """Validate that shard spans EXACTLY tile the leaf and return the
        deduplicated shard list (replicated leaves legitimately appear once
        per host manifest with identical spans).  Gaps — e.g. a lost host
        manifest — or overlaps raise IOError so ``restore_latest`` walks
        back instead of returning uninitialized memory."""
        total = 1
        for d in shape:
            total *= d
        uniq: List[Dict[str, Any]] = []
        seen = set()
        for sh in shards:
            key = tuple(tuple(s) for s in sh["spans"])
            if key in seen:
                continue
            seen.add(key)
            uniq.append(sh)
        vol = 0
        norm = []
        for sh in uniq:
            spans = sh["spans"]
            if len(spans) != len(shape):
                raise IOError(f"leaf {name!r}: shard span rank "
                              f"{len(spans)} != leaf rank {len(shape)}")
            v = 1
            for (a, b), dim in zip(spans, shape):
                if not (0 <= a < b <= dim):
                    raise IOError(f"leaf {name!r}: span [{a},{b}) outside "
                                  f"dim {dim}")
                v *= b - a
            vol += v
            norm.append(spans)
        for i in range(len(norm)):
            for j in range(i + 1, len(norm)):
                if norm[i] and all(max(a1, a2) < min(b1, b2)
                                   for (a1, b1), (a2, b2)
                                   in zip(norm[i], norm[j])):
                    raise IOError(f"leaf {name!r}: overlapping shard spans "
                                  f"{norm[i]} / {norm[j]}")
        if vol != total:
            raise IOError(
                f"leaf {name!r}: shard spans cover {vol} of {total} "
                "elements — missing host manifest or corrupt checkpoint")
        return uniq

    def _decode_payload(self, final: str, sh: Dict[str, Any],
                        want: np.dtype) -> np.ndarray:
        """np.load + CRC verify + codec decode of one shard file."""
        path = os.path.join(final, sh["file"])
        try:
            payload = np.load(path)
        except Exception as e:
            # a corrupted .npy HEADER surfaces as whatever numpy's parser
            # trips over (ValueError, SyntaxError, tokenize.TokenError,
            # EOFError...); normalize to IOError so restore_latest walks
            # back like any other corruption
            raise IOError(f"unreadable shard {path}: "
                          f"{type(e).__name__}: {e}") from e
        if self.verify_crc and "crc32" in sh:
            if crc32_array(payload) != sh["crc32"]:
                raise IOError(f"CRC mismatch in {path}")
        if "codec" in sh:
            payload = CODECS[sh["codec"]["name"]].decode(payload, sh["codec"])
        if payload.dtype.kind == "V" and payload.dtype.itemsize == want.itemsize:
            # ml_dtypes customs (bf16, fp8) round-trip .npy as raw void
            # bytes; reinterpret rather than cast
            payload = payload.view(want)
        return payload

    def _find_shard(self, step: int, name: str, spans,
                    man_cache: Dict[int, Dict],
                    want_sid: Optional[str] = None) -> Dict[str, Any]:
        """The shard entry for (name, spans) in ``step``'s manifests — the
        delta chain's parent lookup.  Raises IOError when the parent step
        or the matching shard is gone (child invalidated), or when
        ``want_sid`` doesn't match the shard's lineage id: the parent step
        NUMBER was regenerated after a walk-back + resume and holds a
        different training trajectory — mixing generations would restore a
        frankenstate with every per-file CRC passing."""
        if step not in man_cache:
            if not os.path.isdir(self._final(step)):
                raise IOError(f"delta parent step {step} is missing")
            man_cache[step] = self._load_manifests(step)
        entry = man_cache[step].get(name)
        if entry is None:
            raise IOError(f"delta parent step {step} has no leaf {name!r}")
        for sh in entry["shards"]:
            if sh["spans"] == spans:
                if want_sid is not None and sh.get("sid") != want_sid:
                    raise IOError(
                        f"delta parent step {step} was regenerated "
                        f"(lineage {sh.get('sid')} != referenced "
                        f"{want_sid}) — stale chain invalidated")
                return sh
        raise IOError(f"delta parent step {step} has no shard of {name!r} "
                      f"with spans {spans}")

    def _fill_blocks(self, step: int, name: str, spans, block: int,
                     needed: Set[int], out: np.ndarray, want: np.dtype,
                     man_cache: Dict[int, Dict], depth: int = 0,
                     want_sid: Optional[str] = None) -> None:
        """Copy the requested delta blocks of shard (name, spans) at
        ``step`` into ``out`` (flat, nblocks*block elements), resolving
        parent references recursively.  Any missing/corrupt link — or a
        parent whose lineage id shows the step was regenerated — raises
        IOError: the whole chain is invalidated."""
        if depth > 64:
            raise IOError(f"delta chain deeper than 64 at step {step} "
                          f"({name!r}) — corrupt parent links")
        final = self._final(step)
        sh = self._find_shard(step, name, spans, man_cache, want_sid)
        d = sh.get("delta")
        if d is None:               # a full shard terminates the chain
            flat = self._decode_payload(final, sh, want).reshape(-1)
            for b in needed:
                seg = flat[b * block:(b + 1) * block]
                if seg.size == 0:
                    raise IOError(f"delta block {b} of {name!r} out of "
                                  f"range in full shard at step {step}")
                out[b * block:b * block + seg.size] = seg
            return
        if d["block"] != block:
            raise IOError(f"delta block size changed mid-chain for "
                          f"{name!r} at step {step}")
        pos = {int(b): j for j, b in enumerate(d["local"])}
        here = [b for b in needed if b in pos]
        if here:
            if sh.get("file") is None:
                raise IOError(f"delta shard of {name!r} at step {step} "
                              "lists local blocks but has no file")
            flat = self._decode_payload(final, sh, want).reshape(-1)
            if flat.size < len(pos) * block:
                raise IOError(f"delta shard of {name!r} at step {step} "
                              f"truncated: {flat.size} < {len(pos) * block}")
            for b in here:
                j = pos[b]
                out[b * block:(b + 1) * block] = \
                    flat[j * block:(j + 1) * block]
        rest = needed.difference(here)
        if not rest:
            return
        pmap: Dict[int, int] = {}
        for ps, bs in d["parents"].items():
            for b in bs:
                pmap[int(b)] = int(ps)
        sids = d.get("parent_sids", {})
        byp: Dict[int, Set[int]] = {}
        for b in rest:
            if b not in pmap:
                raise IOError(f"delta block {b} of {name!r} unresolved at "
                              f"step {step} — corrupt manifest")
            byp.setdefault(pmap[b], set()).add(b)
        for s, bs in sorted(byp.items()):
            self._fill_blocks(s, name, spans, block, bs, out, want,
                              man_cache, depth + 1,
                              want_sid=sids.get(str(s)))

    def _assemble_delta(self, step: int, name: str, entry: Dict[str, Any],
                        sh: Dict[str, Any],
                        man_cache: Dict[int, Dict]) -> np.ndarray:
        d = sh["delta"]
        block, nb, size = d["block"], d["nblocks"], d["size"]
        want = np.dtype(entry["dtype"])
        out = np.zeros(nb * block, dtype=want)
        self._fill_blocks(step, name, sh["spans"], block, set(range(nb)),
                          out, want, man_cache)
        return out[:size]

    def _load_shard(self, step: int, name: str, entry: Dict[str, Any],
                    sh: Dict[str, Any],
                    man_cache: Dict[int, Dict]) -> np.ndarray:
        want = np.dtype(entry["dtype"])
        if "delta" in sh:
            payload = self._assemble_delta(step, name, entry, sh, man_cache)
        else:
            payload = self._decode_payload(self._final(step), sh, want)
        return payload.astype(want, copy=False)

    def _read_leaf(self, step: int, name: str, entry: Dict[str, Any], *,
                   man_cache: Optional[Dict[int, Dict]] = None,
                   parallel: bool = True) -> np.ndarray:
        """Reassemble one leaf from its shard spans; shard loads run on the
        I/O pool unless already inside it (parallel=False avoids nesting).
        Spans are validated to exactly tile the leaf first — a gap (lost
        host manifest) or overlap raises IOError instead of leaving
        uninitialized memory in the output."""
        man_cache = {} if man_cache is None else man_cache
        shape = tuple(entry["shape"])
        shards = self._check_tiling(name, shape, entry["shards"])
        if parallel and len(shards) > 1:
            payloads = self._engine.read_many(
                [functools.partial(self._load_shard, step, name, entry, sh,
                                   man_cache) for sh in shards])
        else:
            payloads = [self._load_shard(step, name, entry, sh, man_cache)
                        for sh in shards]
        out: Optional[np.ndarray] = None
        for sh, payload in zip(shards, payloads):
            spans = sh["spans"]
            if not spans:  # scalar
                return payload.reshape(shape)
            if out is None:
                out = np.empty(shape, dtype=entry["dtype"])
            sl = tuple(slice(a, b) for a, b in spans)
            out[sl] = payload.reshape(tuple(b - a for a, b in spans))
        assert out is not None, entry
        return out.reshape(shape)

    def _fetch_leaves(self, step: int, merged: Dict[str, Any],
                      names: List[str],
                      man_cache: Dict[int, Dict]) -> Dict[str, np.ndarray]:
        """Load many leaves concurrently (leaf-level parallelism; shard-level
        kicks in instead when a single leaf dominates)."""
        if len(names) > 1:
            arrs = self._engine.read_many(
                [functools.partial(self._read_leaf, step, n, merged[n],
                                   man_cache=man_cache, parallel=False)
                 for n in names])
        else:
            arrs = [self._read_leaf(step, n, merged[n], man_cache=man_cache)
                    for n in names]
        return dict(zip(names, arrs))

    def restore(self, *, step: Optional[int] = None, like=None,
                shardings=None) -> Tuple[Any, Optional[Dict]]:
        """Returns (state, local_state).

        ``like``: template pytree (arrays or ShapeDtypeStructs) defining the
        tree structure.  ``shardings``: matching pytree of Shardings (or
        None -> numpy arrays) — may describe a DIFFERENT mesh than the one
        that saved (elastic restore: reassembled from spans).

        Restoring also resets the in-memory delta base: a restore implies a
        rollback, so the next ``save`` is always a full checkpoint (delta
        references into post-rollback steps would be meaningless).
        """
        # join (but don't consume the error of) any in-flight async writer
        # FIRST: its completion handler updates _delta_base, and running it
        # after the reset below would resurrect a pre-rollback base
        if self._writer is not None:
            self._writer.join()
            self._writer = None
        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {self.directory}")
        merged = self._load_manifests(step)
        man_cache: Dict[int, Dict] = {step: merged}

        if like is None:
            # rebuild a nested dict from dotted names
            cache = self._fetch_leaves(step, merged, list(merged), man_cache)
            root: Dict[str, Any] = {}
            for name in merged:
                parts = name.split(".")
                d = root
                for p in parts[:-1]:
                    d = d.setdefault(p, {})
                d[parts[-1]] = cache[name]
            state = root
        else:
            named = _flatten_named(like)
            for name, _ in named:
                if name not in merged:
                    raise KeyError(f"leaf {name!r} missing from checkpoint "
                                   f"{self._final(step)}")
            flat_shardings = (jax.tree_util.tree_flatten_with_path(shardings)[0]
                              if shardings is not None else None)
            cache = self._fetch_leaves(step, merged, [n for n, _ in named],
                                       man_cache)
            rebuilt = []
            for i, (name, leaf) in enumerate(named):
                sh = flat_shardings[i][1] if flat_shardings is not None else None
                arr = cache[name]
                rebuilt.append(arr if sh is None else jax.device_put(arr, sh))
            state = jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(like), rebuilt)

        local = None
        lp = os.path.join(self._final(step), f"local_h{self.host_id}.json")
        if os.path.exists(lp):
            local = read_json(lp)
        # rollback hygiene: never let a post-restore save reference
        # pre-restore steps as delta parents
        self._delta_base = {}
        self._chain_len = 0
        return state, local

    def restore_local_shards(self, step: int) -> List[Dict]:
        """Load every per-shard local-scope file of ``step``, ordered by
        shard index (reads run on the I/O pool).  Returns [] when the
        checkpoint predates local-scope saving — callers fall back to the
        host-scope local dict."""
        final = self._final(step)
        found = []
        for fn in os.listdir(final):
            m = _LOCAL_SHARD_RE.match(fn)
            if m:
                found.append((int(m.group(1)), os.path.join(final, fn)))
        found.sort()
        return self._engine.read_many(
            [functools.partial(read_json, p) for _, p in found])

    def restore_latest(self, *, like=None, shardings=None,
                       candidates: Optional[List[int]] = None,
                       with_local_shards: bool = False
                       ) -> Tuple[Any, Optional[Dict], int, List[Tuple[int, str]]]:
        """Restore the newest checkpoint that actually verifies.

        On a corrupt checkpoint (CRC mismatch, truncated shard, unreadable
        or incomplete manifest, a broken delta chain — a corrupt parent
        invalidates every delta that references it) it walks back through
        the retained ``keep`` history instead of failing the whole restore.
        ``candidates`` overrides the try-order (first entry tried first) —
        e.g. the SDC layer passes scrub-verified steps first.
        ``with_local_shards``: also load the per-shard local-scope files as
        part of candidate verification, so a corrupt/truncated
        ``local_s<k>.json`` walks back like any other corrupt shard instead
        of killing the restore.

        Returns (state, local_state, step, skipped) — or, with
        ``with_local_shards``, (state, local_state, shard_dicts, step,
        skipped) — where ``skipped`` is [(step, reason), ...] for every
        checkpoint that had to be passed over — callers should surface it:
        each entry is lost work.
        """
        if candidates is None:
            candidates = list(reversed(self.all_steps()))
        skipped: List[Tuple[int, str]] = []
        for s in candidates:
            try:
                state, local = self.restore(step=s, like=like,
                                            shardings=shardings)
                if with_local_shards:
                    shard_dicts = self.restore_local_shards(s)
                    return state, local, shard_dicts, s, skipped
                return state, local, s, skipped
            except (IOError, ValueError, json.JSONDecodeError) as e:
                # NOT KeyError: a template leaf missing from the manifest
                # is a caller bug that affects every candidate identically
                # — walking back would silently discard all progress
                skipped.append((s, f"{type(e).__name__}: {e}"))
        detail = "; ".join(f"step {s}: {r}" for s, r in skipped)
        raise FileNotFoundError(
            f"no restorable checkpoint under {self.directory}"
            + (f" (skipped {detail})" if detail else ""))
