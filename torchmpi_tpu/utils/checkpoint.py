"""Distributed checkpoint / resume.

The reference has **no** checkpointing (SURVEY.md §5: users relied on
``torch.save``; nothing distributed-aware exists) — this is a deliberate
capability addition for the TPU rebuild: engine state (params, optimizer
state, mutable model state, step counters) and parameter-server centers are
saved via Orbax, which handles sharded arrays and multi-host coordination
natively.

Two formats live here:

- the **orbax** format (:func:`save_engine`/:func:`restore_engine`):
  cooperative multi-host saves of live (possibly non-addressable) arrays.
  Layout metadata (world size, sharding, step, structure fingerprint) is
  stamped in an atomically-written ``meta.json`` header, and restore
  validates it up front — a mismatched world/sharding fails loudly with
  the mismatch *named* instead of shape-erroring mid-load.
- the **portable sharded** format (:func:`save_engine_sharded` /
  :func:`restore_engine_sharded` / :func:`reshape_sharded`): one plain
  ``.npy`` file per (leaf, shard rank) under a contiguous
  :class:`~..reshard.Layout`, published via an atomic ``CURRENT``
  pointer (write temp dir + fsync + rename — a save killed at ANY point
  leaves the previous checkpoint intact). Because shards are files, an
  N-way checkpoint reshapes onto an M-way world **offline** with bounded
  memory (mmap'd reads through the reshard executor's chunked scratch;
  ``python -m torchmpi_tpu.reshard``) or transparently at restore time.
"""

from __future__ import annotations

import hashlib
import json
import os
import secrets
from pathlib import Path
from typing import Any, Dict, List, Optional

import jax
import numpy as np

SHARDED_FORMAT = "tmsc1"


def _ckptr():
    import orbax.checkpoint as ocp

    return ocp.PyTreeCheckpointer()


def _engine_state(engine) -> Dict[str, Any]:
    state = {"params": engine.params, "opt_state": engine.opt_state}
    if engine.model_state is not None:
        state["model_state"] = engine.model_state
    return state


class CheckpointMismatchError(RuntimeError):
    """A checkpoint's layout header disagrees with the restore target.

    Raised BEFORE any state is touched, naming the mismatched field —
    the alternative is a shape error halfway through an orbax load with
    half the engine already overwritten."""


def _fsync_file(path: Path) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _atomic_write_text(path: Path, text: str) -> None:
    """temp + fsync + rename: readers see the old bytes or the new bytes,
    never a torn file — and a crash mid-write leaves the old file."""
    tmp = path.with_name(f"{path.name}.tmp-{os.getpid()}")
    tmp.write_text(text)
    _fsync_file(tmp)
    os.replace(tmp, path)
    try:  # land the rename itself before callers rely on it
        dirfd = os.open(path.parent, os.O_RDONLY)
        os.fsync(dirfd)
        os.close(dirfd)
    except OSError:
        pass


def _tree_fingerprint(state: Dict[str, Any]) -> str:
    """Structure fingerprint: tree shape + per-leaf (path, shape, dtype).
    Two engines with the same fingerprint can exchange checkpoints; a
    mismatch names exactly what diverged (model width, optimizer kind)."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(state)
    desc = [
        (jax.tree_util.keystr(p), tuple(np.shape(a)),
         np.dtype(getattr(a, "dtype", None) or np.asarray(a).dtype).str)
        for p, a in leaves
    ]
    return hashlib.sha1(repr(desc).encode()).hexdigest()[:12]


def _layout_meta(engine, step: int, extra: Optional[Dict],
                 state: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    return {
        "step": int(step),
        "world": int(engine.comm.size),
        "sharding": engine.param_sharding,
        "fingerprint": _tree_fingerprint(
            _engine_state(engine) if state is None else state
        ),
        **(extra or {}),
    }


def _check_layout(meta: Dict[str, Any], engine, path,
                  allow_world_mismatch: bool = False) -> None:
    """Validate a checkpoint header against the restore target, naming
    the first mismatch (the satellite contract: fail loudly up front)."""
    want_fp = _tree_fingerprint(_engine_state(engine))
    if meta.get("fingerprint") and meta["fingerprint"] != want_fp:
        raise CheckpointMismatchError(
            f"checkpoint {path} was saved from a different model/optimizer "
            f"structure (fingerprint {meta['fingerprint']} != engine "
            f"{want_fp}): same architecture + optimizer required"
        )
    if meta.get("sharding") and meta["sharding"] != engine.param_sharding:
        raise CheckpointMismatchError(
            f"checkpoint {path} holds param_sharding="
            f"{meta['sharding']!r} state but the engine runs "
            f"{engine.param_sharding!r}; rebuild the engine with "
            f"param_sharding={meta['sharding']!r} (the portable sharded "
            "format reshapes world sizes, not sharding strategies)"
        )
    world = meta.get("world")
    if (
        not allow_world_mismatch
        and world is not None
        and int(world) != engine.comm.size
        and engine.param_sharding != "replicated"  # replicated state is
        # world-independent: the same full arrays land on any mesh
    ):
        raise CheckpointMismatchError(
            f"checkpoint {path} was saved from a {world}-way world but "
            f"this engine spans {engine.comm.size} ranks; reshape it "
            f"(`python -m torchmpi_tpu.reshard --from {world} "
            f"--to {engine.comm.size} <ckpt> <out>`) or use "
            "restore_engine_sharded, which reshards transparently"
        )


def save_engine(path, engine, step: int = 0, extra: Optional[Dict] = None) -> None:
    """Save an AllReduceSGDEngine's full training state.

    Multi-process (multi-controller) runs hand the LIVE jax arrays to
    Orbax — sharded/non-addressable arrays (fsdp over processes) are
    written cooperatively by all hosts; ``jax.device_get`` would raise on
    them. Single-process saves go through host numpy (robust for typed
    optax nodes and independent of live placement).

    ``meta.json`` is the layout header (world size, sharding, step,
    structure fingerprint), written atomically (temp + fsync + rename)
    and LAST — so a save killed mid-write never publishes a header whose
    state payload is torn, and restore can validate before loading.
    """
    path = Path(path).resolve()
    path.mkdir(parents=True, exist_ok=True)
    if jax.process_count() > 1:
        state = _engine_state(engine)
    else:
        state = jax.tree_util.tree_map(
            lambda a: jax.device_get(a), _engine_state(engine)
        )
    _ckptr().save(path / "state", state, force=True)
    if jax.process_index() == 0:
        _atomic_write_text(
            path / "meta.json", json.dumps(_layout_meta(engine, step, extra))
        )


def restore_engine(path, engine) -> Dict[str, Any]:
    """Restore state saved by :func:`save_engine` into the engine. Device
    placement follows each live leaf's CURRENT sharding — replicated
    engines restore replicated, fsdp engines restore sharded (densifying
    to replicated would silently drop ZeRO-3 and force a recompile).
    Returns the meta dict (incl. ``step``).

    The layout header is validated FIRST: a checkpoint from a different
    world size, sharding mode, or model structure raises
    :class:`CheckpointMismatchError` naming the mismatch, before any of
    the engine's state is touched.

    The engine's current state is passed as the restore template so typed
    pytree nodes (optax namedtuple states like ScaleByAdamState) come back
    with their original structure instead of plain lists/dicts."""
    path = Path(path).resolve()
    meta = json.loads((path / "meta.json").read_text())
    _check_layout(meta, engine, path)
    live = _engine_state(engine)
    if jax.process_count() > 1:
        # cooperative multi-host restore straight into the live shardings
        import orbax.checkpoint as ocp

        restore_args = ocp.checkpoint_utils.construct_restore_args(live)
        state = _ckptr().restore(
            path / "state", item=live, restore_args=restore_args
        )
    else:
        template = jax.tree_util.tree_map(lambda a: jax.device_get(a), live)
        restored = _ckptr().restore(path / "state", item=template)
        state = jax.tree_util.tree_map(
            lambda cur, new: jax.device_put(new, cur.sharding), live, restored
        )

    engine.params = state["params"]
    engine.opt_state = state["opt_state"]
    if "model_state" in state and engine.model_state is not None:
        engine.model_state = state["model_state"]
    return json.loads((path / "meta.json").read_text())


# ---------------------------------------------------------------------------
# portable sharded format: per-(leaf, rank) .npy shards + atomic CURRENT
# pointer. The on-disk twin of the live fsdp/zero1 layouts — and the unit
# the offline reshaper (`python -m torchmpi_tpu.reshard`) operates on.
# ---------------------------------------------------------------------------


def _sharded_trees(engine) -> Dict[str, str]:
    """tree name -> 'sharded' | 'replicated' under the engine's mode.
    The PORTABLE layout is defined here (flat contiguous shards), not by
    live device placement: fsdp shards params+opt, zero1 shards only the
    optimizer state, replicated engines shard nothing."""
    kind = {
        "fsdp": {"params": "sharded", "opt_state": "sharded"},
        "zero1": {"params": "replicated", "opt_state": "sharded"},
        "replicated": {"params": "replicated", "opt_state": "replicated"},
    }[engine.param_sharding]
    out = dict(kind)
    if engine.model_state is not None:
        out["model_state"] = kind["params"]
    return out


def _leaf_records(state: Dict[str, Any], kinds: Dict[str, str]) -> List[dict]:
    records = []
    for tree_name in sorted(state):
        leaves, _ = jax.tree_util.tree_flatten_with_path(state[tree_name])
        for p, a in leaves:
            arr_dtype = np.dtype(getattr(a, "dtype", np.asarray(a).dtype))
            records.append({
                "tree": tree_name,
                "path": jax.tree_util.keystr(p),
                "shape": list(np.shape(a)),
                "dtype": arr_dtype.str,
                "n": int(np.prod(np.shape(a), dtype=np.int64)),
                "kind": kinds[tree_name],
            })
    return records


def _shard_file(data_dir: Path, leaf_idx: int, rank: Optional[int]) -> Path:
    name = (
        f"leaf{leaf_idx}.full.npy" if rank is None
        else f"leaf{leaf_idx}.rank{rank}.npy"
    )
    return data_dir / name


def current_data_dir(path) -> Path:
    """The live data directory a sharded checkpoint's CURRENT points at."""
    path = Path(path).resolve()
    cur = (path / "CURRENT").read_text().strip()
    return path / cur


def read_sharded_meta(path) -> Dict[str, Any]:
    meta = json.loads((current_data_dir(path) / "meta.json").read_text())
    if meta.get("format") != SHARDED_FORMAT:
        raise CheckpointMismatchError(
            f"{path} is not a {SHARDED_FORMAT} sharded checkpoint "
            f"(format={meta.get('format')!r})"
        )
    return meta


def save_engine_sharded(
    path, engine, step: int = 0, extra: Optional[Dict] = None,
    world: Optional[int] = None, state: Optional[Dict[str, Any]] = None,
) -> Path:
    """Save the engine's state as a portable sharded checkpoint.

    Every leaf is flattened and cut into ``world`` contiguous shards
    (:class:`~..reshard.Layout` — byte-identical to what a fresh
    ``world``-way scatter would place on each rank); replicated trees
    (zero1 params) store ONE full copy. All files land in a fresh
    ``data-<token>/`` directory, fsync'd, and only then does the atomic
    ``CURRENT`` pointer swing to it — a save killed at any point (power
    loss included) leaves the previous checkpoint fully intact, and the
    superseded data dir is garbage-collected on the NEXT successful save.

    Single-controller only (every leaf must be addressable); multi-host
    jobs use the orbax format and reshape offline.

    ``state`` overrides the engine's live trees: an async caller (the
    engine's ``checkpoint_every`` hook) passes the reference snapshot
    it took on the step thread, so a save never serializes a tree the
    next step() already half-replaced. Every published checkpoint is
    registered as the newest rollback artifact
    (:func:`~..supervise.checkpoints.register_checkpoint`).
    """
    from ..reshard import Layout

    if jax.process_count() > 1:
        raise RuntimeError(
            "save_engine_sharded is single-controller only (leaves must "
            "be host-addressable); multi-host jobs save via save_engine "
            "and reshape offline with `python -m torchmpi_tpu.reshard`"
        )
    path = Path(path).resolve()
    path.mkdir(parents=True, exist_ok=True)
    world = int(world or engine.comm.size)
    live_state = _engine_state(engine) if state is None else state
    state = jax.tree_util.tree_map(
        lambda a: np.asarray(jax.device_get(a)), live_state
    )
    kinds = _sharded_trees(engine)
    records = _leaf_records(state, kinds)
    meta = {
        "format": SHARDED_FORMAT,
        **_layout_meta(engine, step, extra, state=live_state),
        "world": world,
        "leaves": records,
    }
    token = secrets.token_hex(4)
    data_dir = path / f"data-{token}"
    tmp_dir = path / f".tmp-{token}"
    tmp_dir.mkdir()
    leaves = [
        a for tree_name in sorted(state)
        for a in jax.tree_util.tree_leaves(state[tree_name])
    ]
    layout = Layout(world)
    for i, (rec, arr) in enumerate(zip(records, leaves)):
        flat = np.asarray(arr).reshape(-1)
        if rec["kind"] == "replicated":
            files = [(_shard_file(tmp_dir, i, None), flat)]
        else:
            files = [
                (_shard_file(tmp_dir, i, r), flat[s:e])
                for r, (s, e) in enumerate(layout.intervals(rec["n"]))
            ]
        for f, data in files:
            np.save(f, data)
            _fsync_file(f)
    (tmp_dir / "meta.json").write_text(json.dumps(meta))
    _fsync_file(tmp_dir / "meta.json")
    os.replace(tmp_dir, data_dir)  # the complete payload becomes visible
    prev = None
    try:
        prev = current_data_dir(path)
    except (OSError, ValueError):
        pass
    _atomic_write_text(path / "CURRENT", data_dir.name)
    # the artifact is published: register it as the newest rollback
    # target (what DataLoss messages and the supervisor's rollback name)
    from ..supervise import checkpoints as _registry

    _registry.register_checkpoint(path, step)
    # GC the superseded payload (and any orphaned temp dirs from saves
    # that died before publishing) only AFTER the pointer swung
    import shutil

    for stale in list(path.glob(".tmp-*")) + (
        [prev] if prev is not None and prev != data_dir else []
    ):
        if stale.name == data_dir.name:
            continue
        shutil.rmtree(stale, ignore_errors=True)
    return data_dir


def _assemble_leaf(data_dir: Path, leaf_idx: int, rec: dict,
                   world: int) -> np.ndarray:
    """Reassemble one leaf's full flat array from its shard files."""
    if rec["kind"] == "replicated":
        return np.load(_shard_file(data_dir, leaf_idx, None))
    parts = [
        np.load(_shard_file(data_dir, leaf_idx, r)) for r in range(world)
    ]
    return np.concatenate(parts) if parts else np.empty(0, rec["dtype"])


def restore_engine_sharded(path, engine) -> Dict[str, Any]:
    """Restore a portable sharded checkpoint into the engine — from ANY
    source world size: when the checkpoint's world differs from the
    engine's, the shard files are redistributed through the reshard
    planner on the way in (each live leaf receives exactly the bytes a
    fresh ``engine.comm.size``-way scatter of the assembled state would
    give it). Structure/sharding mismatches still fail loudly."""
    path = Path(path).resolve()
    meta = read_sharded_meta(path)
    _check_layout(meta, engine, path, allow_world_mismatch=True)
    data_dir = current_data_dir(path)
    world = int(meta["world"])
    live = _engine_state(engine)
    leaves, treedef = jax.tree_util.tree_flatten(live)
    records = meta["leaves"]
    if len(records) != len(leaves):
        raise CheckpointMismatchError(
            f"checkpoint {path} holds {len(records)} leaves but the "
            f"engine has {len(leaves)}"
        )
    restored = []
    for i, (rec, cur) in enumerate(zip(records, leaves)):
        full = _assemble_leaf(data_dir, i, rec, world)
        arr = full.reshape(tuple(rec["shape"]))
        restored.append(jax.device_put(arr, cur.sharding))
    state = jax.tree_util.tree_unflatten(treedef, restored)
    engine.params = state["params"]
    engine.opt_state = state["opt_state"]
    if "model_state" in state and engine.model_state is not None:
        engine.model_state = state["model_state"]
    return meta


def reshape_sharded(
    src_path, dst_path, to_world: int,
    chunk_bytes: Optional[int] = None,
) -> Dict[str, Any]:
    """Offline N-way -> M-way reshape of a sharded checkpoint with
    bounded memory: source shards are mmap'd read-only, target shards are
    preallocated memmaps, and every byte moves through the reshard
    executor's single chunked scratch buffer — the full array is never
    materialized, regardless of checkpoint size. Returns a stats dict
    incl. the asserted ``peak_scratch_bytes`` bound.
    """
    from ..reshard import Layout, Redistributor

    src_path, dst_path = Path(src_path).resolve(), Path(dst_path).resolve()
    if int(to_world) < 1:
        raise ValueError(f"--to world must be >= 1, got {to_world}")
    meta = read_sharded_meta(src_path)
    src_dir = current_data_dir(src_path)
    from_world = int(meta["world"])
    dst_path.mkdir(parents=True, exist_ok=True)
    token = secrets.token_hex(4)
    tmp_dir = dst_path / f".tmp-{token}"
    tmp_dir.mkdir()
    src_layout, dst_layout = Layout(from_world), Layout(int(to_world))
    stats = {
        "from": from_world, "to": int(to_world), "leaves": len(meta["leaves"]),
        "peak_scratch_bytes": 0, "largest_shard_bytes": 0,
        "moved_bytes": 0, "plans": [],
    }
    for i, rec in enumerate(meta["leaves"]):
        dt = np.dtype(rec["dtype"])
        n = int(rec["n"])
        if rec["kind"] == "replicated":
            # one full copy in, one full copy out — streamed in chunks
            src = np.load(_shard_file(src_dir, i, None), mmap_mode="r")
            out = np.lib.format.open_memmap(
                _shard_file(tmp_dir, i, None), mode="w+", dtype=dt,
                shape=(n,),
            )
            from ..reshard.core import chunk_elems_for, chunk_spans

            for s, e in chunk_spans(n, chunk_elems_for(dt.itemsize,
                                                       chunk_bytes)):
                out[s:e] = src[s:e]
            out.flush()
            continue
        rd = Redistributor(n, dt, src_layout, dst_layout, chunk_bytes)
        srcs = [
            np.load(_shard_file(src_dir, i, r), mmap_mode="r")
            for r in range(from_world)
        ]
        outs = [
            np.lib.format.open_memmap(
                _shard_file(tmp_dir, i, r), mode="w+", dtype=dt,
                shape=(max(0, e - s),),
            )
            for r, (s, e) in enumerate(dst_layout.intervals(n))
        ]

        def read(rank, off, view):
            view[:] = srcs[rank][off:off + view.shape[0]]

        def write(rank, off, values):
            outs[rank][off:off + values.shape[0]] = values

        rd.run(read, write)
        for o in outs:
            o.flush()
        stats["peak_scratch_bytes"] = max(
            stats["peak_scratch_bytes"], rd.peak_scratch_bytes
        )
        stats["largest_shard_bytes"] = max(
            stats["largest_shard_bytes"],
            max((a.nbytes for a in srcs), default=0),
            max((a.nbytes for a in outs), default=0),
        )
        stats["moved_bytes"] += sum(t.n for t in rd.transfers) * dt.itemsize
        stats["plans"].append(rd.plan.plan_id)
    new_meta = dict(meta, world=int(to_world))
    (tmp_dir / "meta.json").write_text(json.dumps(new_meta))
    _fsync_file(tmp_dir / "meta.json")
    for f in tmp_dir.iterdir():
        _fsync_file(f)
    data_dir = dst_path / f"data-{token}"
    os.replace(tmp_dir, data_dir)
    _atomic_write_text(dst_path / "CURRENT", data_dir.name)
    return stats


def save_parameter_servers(path, ps_group) -> None:
    """Save a PSGroup's center values (assembled from shards)."""
    path = Path(path).resolve()
    path.mkdir(parents=True, exist_ok=True)
    centers = [srv.receive().wait() for srv in ps_group.servers]
    _ckptr().save(path / "ps_centers", {"centers": centers}, force=True)


def restore_parameter_servers(path, ps_group) -> None:
    """Restore PS centers: each server's shards are overwritten via the
    'copy' rule (a collective in the reference; here applied per shard)."""
    path = Path(path).resolve()
    state = _ckptr().restore(path / "ps_centers")
    for srv, center in zip(ps_group.servers, state["centers"]):
        srv.send(np.asarray(center), rule="copy").wait()
