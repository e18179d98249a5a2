"""Erasure-coded distributed checkpointing (TOFEC-integrated).

Every checkpoint leaf (one array of the params/opt-state pytree) is:
  1. serialized (raw bytes + dtype/shape manifest entry, crc32 checksum),
  2. RS-encoded into n strips of size ⌈bytes/k⌉ through the unified batched
     codec engine (:mod:`repro.coding.codec` — the Pallas kernel on a TPU,
     the numpy oracle elsewhere); leaves sharing an (n, k) plan are encoded
     in ONE batched kernel call,
  3. written as n independent objects ``{prefix}/step{s}/{leaf}/strip{i}``.

Restore fetches any k surviving strips per leaf and batch-decodes all leaves
that share (n, k, strip size) in one codec call — the engine accepts a
per-item ``present`` matrix, so heterogeneous erasure patterns across
leaves still form a single batch. Node/object loss up to n−k per leaf is
invisible. The chunking level k is chosen per-write by the TOFEC controller
from the writer backlog: an idle writer uses high k (many small parallel
strips → low write latency), a backlogged writer drops to k=1 (one big
strip + parity → max throughput), which is exactly the paper's
throughput-delay trade-off transplanted to checkpoints.

``AsyncCheckpointer`` overlaps encode+write with training steps.
"""

from __future__ import annotations

import dataclasses
import json
import queue as _queue
import threading
import zlib

import jax
import numpy as np

from repro.coding import codec as codec_mod
from repro.core.controller import Policy, StaticPolicy
from repro.storage.backend import ObjectStore, StorageError


@dataclasses.dataclass
class CodingPlan:
    n: int
    k: int


def _leaf_paths(tree) -> list[tuple[str, np.ndarray]]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for path, leaf in flat:
        name = "/".join(str(p.key) if hasattr(p, "key") else str(p.idx) for p in path)
        out.append((name, np.asarray(leaf)))
    return out


def save_checkpoint(
    store: ObjectStore,
    prefix: str,
    step: int,
    tree,
    *,
    policy: Policy | None = None,
    n_max: int = 8,
    k_max: int = 4,
    pending_hint: int = 0,
    codec: codec_mod.Codec | None = None,
) -> dict:
    """Write one erasure-coded checkpoint; returns the manifest."""
    policy = policy or StaticPolicy(n_max, k_max)
    codec = codec or codec_mod.get_codec()
    leaves = _leaf_paths(tree)
    manifest = {"step": step, "leaves": {}, "format": 1}

    # Pick a plan per leaf, then group by (n, k) so each group shards
    # through ONE batched encode call.
    plans: list[tuple[str, np.ndarray, int, int]] = []
    for name, arr in leaves:
        # Backlog signal = externally pending checkpoint snapshots (the
        # async writer's queue depth) — the TOFEC queue-length analogue.
        # An idle writer chunks finely (low latency); a backlogged one
        # degrades toward k=1 (max throughput), Corollary 1 verbatim.
        q = pending_hint
        n, k = policy.select(q=q, idle=max(0, n_max - 1), cls_id=0)
        n = min(n, n_max)
        k = min(k, k_max, max(1, n))
        plans.append((name, arr, n, k))

    # Group by (n, k, pow2-bucketed strip width): batching pads members to
    # the group max, so bucketing bounds zero-padding waste at 2× per leaf
    # (a lone giant embedding never drags 100 small leaves up to its width)
    # and matches the codec's own internal shape buckets.
    groups: dict[tuple[int, int, int], list[tuple[str, np.ndarray]]] = {}
    for name, arr, n, k in plans:
        strip = codec_mod.Codec.strip_bytes(arr.nbytes, k)
        groups.setdefault((n, k, codec_mod.pow2_bucket(strip, 128)), []).append((name, arr))

    for (n, k, _bucket), members in groups.items():
        payloads = [arr.tobytes() for _, arr in members]
        all_strips = codec.encode_blobs(
            [np.frombuffer(p, np.uint8) for p in payloads], n=n, k=k
        )
        for (name, arr), payload, strips in zip(members, payloads, all_strips):
            strip = strips.shape[1]  # this leaf's own ⌈bytes/k⌉ width
            for si in range(n):
                store.put(f"{prefix}/step{step}/{name}/strip{si}", strips[si].tobytes())
            manifest["leaves"][name] = {
                "shape": list(arr.shape),
                "dtype": str(arr.dtype),
                "n": int(n),
                "k": int(k),
                "bytes": len(payload),
                "strip_bytes": int(strip),
                "crc": zlib.crc32(payload) & 0xFFFFFFFF,
            }
    store.put(f"{prefix}/step{step}/MANIFEST", json.dumps(manifest).encode())
    store.put(f"{prefix}/LATEST", str(step).encode())
    return manifest


def latest_step(store: ObjectStore, prefix: str) -> int | None:
    try:
        return int(store.get(f"{prefix}/LATEST").decode())
    except StorageError:
        return None


def restore_checkpoint(
    store: ObjectStore,
    prefix: str,
    step: int,
    tree_like,
    *,
    codec: codec_mod.Codec | None = None,
) -> object:
    """Rebuild a pytree matching ``tree_like`` from any-k-of-n strips."""
    codec = codec or codec_mod.get_codec()
    manifest = json.loads(store.get(f"{prefix}/step{step}/MANIFEST").decode())
    leaves = _leaf_paths(tree_like)

    # Fetch any k surviving strips per leaf, then batch-decode all leaves
    # sharing (n, k, strip_bytes) in one codec call (per-item present).
    fetched: dict[str, tuple[np.ndarray, tuple[int, ...]]] = {}
    groups: dict[tuple[int, int, int], list[str]] = {}
    for name, _ in leaves:
        meta = manifest["leaves"][name]
        n, k = meta["n"], meta["k"]
        got: dict[int, bytes] = {}
        for si in range(n):
            if len(got) >= k:
                break
            try:
                got[si] = store.get(f"{prefix}/step{step}/{name}/strip{si}")
            except StorageError:
                continue
        if len(got) < k:
            raise StorageError(
                f"{name}: only {len(got)}/{k} strips survive — unrecoverable"
            )
        present = tuple(sorted(got))[:k]
        strips = np.stack([np.frombuffer(got[si], np.uint8) for si in present])
        fetched[name] = (strips, present)
        groups.setdefault((n, k, meta["strip_bytes"]), []).append(name)

    payloads: dict[str, np.ndarray] = {}
    for (n, k, _strip), names in groups.items():
        rows = np.stack([fetched[nm][0] for nm in names])
        present = np.stack([fetched[nm][1] for nm in names])
        decoded = np.asarray(codec.decode(rows, present, n, k))
        for i, nm in enumerate(names):
            nbytes = manifest["leaves"][nm]["bytes"]
            payloads[nm] = decoded[i].reshape(-1)[:nbytes]

    out_leaves = []
    for name, like in leaves:
        meta = manifest["leaves"][name]
        payload = payloads[name]
        if (zlib.crc32(payload.tobytes()) & 0xFFFFFFFF) != meta["crc"]:
            raise StorageError(f"{name}: checksum mismatch after decode")
        arr = np.frombuffer(payload.tobytes(), dtype=meta["dtype"]).reshape(meta["shape"])
        out_leaves.append(arr)
    treedef = jax.tree_util.tree_structure(tree_like)
    return jax.tree_util.tree_unflatten(treedef, out_leaves)


class AsyncCheckpointer:
    """Background checkpoint writer: snapshot on submit, write off-thread.

    ``submit`` copies device arrays to host (blocking only on transfer),
    then a worker thread encodes + writes. ``wait()`` drains the queue.
    """

    def __init__(self, store: ObjectStore, prefix: str, *, policy: Policy | None = None):
        self.store = store
        self.prefix = prefix
        self.policy = policy
        self._q: _queue.Queue = _queue.Queue()
        self._err: Exception | None = None
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def submit(self, step: int, tree) -> None:
        host_tree = jax.tree.map(lambda x: np.asarray(x), tree)
        self._q.put((step, host_tree))

    def _loop(self):
        while True:
            item = self._q.get()
            try:
                if item is None:
                    return
                step, tree = item
                save_checkpoint(
                    self.store, self.prefix, step, tree,
                    policy=self.policy, pending_hint=self._q.qsize(),
                )
            except Exception as e:  # pragma: no cover
                self._err = e
            finally:
                self._q.task_done()

    def wait(self):
        """Block until all submitted checkpoints are durable."""
        self._q.join()
        if self._err:
            raise self._err

    def close(self):
        self._q.put(None)
        self._thread.join()
        if self._err:
            raise self._err
