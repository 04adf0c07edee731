"""Single-buffer host<->device movement of a StateBatch.

The hybrid loop (backend.exec_batch) repacks a batch every round. Moving
the ~50 planes individually pays the fixed cost of a host<->device
transfer ~50 times per round and direction. Both directions instead
serialize the whole batch into ONE u8 buffer:

- up: the host concatenates every plane's raw bytes (numpy, zero-copy
  views), uploads once, and a jitted splitter bitcasts the segments back
  into planes on device;
- down: a jitted flattener concatenates bitcast planes on device, the
  host downloads once and rebuilds a StateBatch of numpy views.

Byte layout is the NamedTuple field order; bitcasts are little-endian on
both sides (numpy ``view`` on the host, ``lax.bitcast_convert_type`` on
TPU/CPU XLA), which the round-trip test pins down.
"""

from functools import partial

import logging
import os

import jax
import jax.numpy as jnp
import numpy as np

from mythril_tpu.laser.tpu import words
from mythril_tpu.robustness import faults

from mythril_tpu.laser.tpu.batch import StateBatch, batch_shapes

log = logging.getLogger(__name__)

# planes the host-side consumers (bridge lift/unpack, coverage merge,
# checkpointing) never read — skipped on the way down to save bytes;
# they are rebuilt as zeros in the host view (a downloaded batch is
# never re-uploaded: every round packs fresh from host states)
_SKIP_DOWN = ("tape_h1", "tape_h2")


# row-sliceable planes: (axis-1 capacity field in BatchConfig is implied
# by the plane's static shape; slicing drops all-zero tail rows). The
# term-tape planes dominate batch bytes, so only they are bucketed —
# everything else ships full-size, keeping the jit-variant count small.
_TAPE_PLANES = (
    "tape_op", "tape_a", "tape_b", "tape_imm", "tape_h1", "tape_h2",
    "tape_meta",
)
_TAPE_BUCKETS = (16, 64, 256, 1024, 4096)


_MONO: list = []  # [bool] memo


def monomorphic() -> bool:
    """One jit variant per transfer direction on accelerator backends.

    Every (tape bucket, absent-group) combination is a separate XLA
    compile of the splitter/flattener, paid inline by the first round
    that meets it, inside the analysis budget. Accelerator backends
    therefore move full-size planes through one executable per
    direction; whether the bytes a smaller variant would save pay for
    its compiles on the chip is not measured yet. CPU keeps the
    polymorphic path: compiles are cheap there and the suite exercises
    it.

    ``MYTHRIL_TPU_MONO_TRANSFER=1|0`` overrides the platform choice
    (debug/experiment hook). Measured r5: pinning 1 on the CPU backend
    is a large NET LOSS on round-heavy workloads (suicide+origin row
    0.5x -> 0.06x host) — full-size plane copies per round dwarf the
    one-time per-bucket variant compiles the polymorphic path pays.
    The platform default stands.
    """
    override = os.environ.get("MYTHRIL_TPU_MONO_TRANSFER")
    if override in ("0", "1"):  # anything else (empty, typo) = unset
        return override == "1"
    if not _MONO:
        try:
            import jax

            _MONO.append(jax.devices()[0].platform != "cpu")
        except Exception as e:
            # do NOT memoize the failure: a transient backend error at
            # init must not pin an accelerator process to the
            # polymorphic path and its per-bucket recompiles forever
            log.debug("device probe failed, assuming cpu for now: %s", e)
            return False
    return _MONO[0]

# tape_imm is carried FLAT ([L, T*NDIGITS]) so the step kernel keeps one
# canonical 2D layout (symtape._alloc_impl); its per-row column count
# scales accordingly when slicing/padding the used-row prefix
def _tape_cols(name: str, rows: int) -> int:
    return rows * words.NDIGITS if name == "tape_imm" else rows


def _bucket(n: int, cap: int) -> int:
    for b in _TAPE_BUCKETS:
        if n <= b and b <= cap:
            return b
    return cap


def _lane_bucket(n: int, cap: int) -> int:
    """Smallest power-of-two lane count covering ``n`` (min 16, max
    ``cap``): power-of-two buckets keep the flattener's jit-variant
    count logarithmic in the lane dimension."""
    b = 16
    while b < n:
        b <<= 1
    return min(b, cap)


# skippable plane groups for the upload. Presence is tracked per GROUP
# (one bit each), not per plane: the presence tuple is part of the
# splitter's static jit key, so per-plane granularity would let the
# compile-variant count grow combinatorially with whatever mix of
# states each round stages. Three bits x tape buckets stays bounded.
_UP_GROUPS = {
    "symbolic": (
        "stack_sym", "tape_op", "tape_a", "tape_b", "tape_imm", "tape_h1",
        "tape_h2", "tape_meta", "tape_len", "path_id", "path_sign",
        "path_meta", "path_len",
        "msym_off", "msym_id", "msym_used", "skey_sym", "sval_sym",
        "calldata_symbolic", "storage_symbolic", "cdsize_sym",
        "caller_sym", "callvalue_sym", "origin_sym", "balance_sym",
    ),
    "memory": ("memory", "mem_words"),
    "storage": ("storage_key", "storage_val", "storage_used"),
}
_GROUP_OF = {
    plane: group for group, planes in _UP_GROUPS.items() for plane in planes
}


def serialize_segments(arrays) -> np.ndarray:
    """Host side: raw little-endian bytes of ``arrays``, concatenated."""
    if not arrays:
        return np.zeros(0, np.uint8)
    return np.concatenate(
        [np.ascontiguousarray(a).view(np.uint8).ravel() for a in arrays]
    )


def split_segments(buf, spec):
    """Device side of :func:`serialize_segments`: walk the buffer and
    rebuild each ``(shape, dtype_str)`` segment (bools via ``!= 0``).
    Runs under jit with ``spec`` static."""
    out = []
    off = 0
    for shape, dtype_str in spec:
        dtype = np.dtype(dtype_str)
        nbytes = int(np.prod(shape)) * dtype.itemsize
        seg = jax.lax.dynamic_slice(buf, (off,), (nbytes,))
        off += nbytes
        if dtype == np.bool_:
            out.append(seg.reshape(shape) != 0)
        elif dtype.itemsize == 1:
            out.append(seg.reshape(shape).view(jnp.dtype(dtype)))
        else:
            out.append(
                jax.lax.bitcast_convert_type(
                    seg.reshape(tuple(shape) + (dtype.itemsize,)),
                    jnp.dtype(dtype),
                )
            )
    return out


@partial(jax.jit, static_argnames=("spec",))
def _split_jit(buf, spec):
    return tuple(split_segments(buf, spec))


def upload_segments(arrays):
    """One-buffer upload of arbitrary host arrays; returns the device
    arrays. The segment spec is derived from the inputs."""
    spec = tuple(
        (tuple(a.shape), np.dtype(a.dtype).str) for a in arrays
    )
    return _split_jit(jnp.asarray(serialize_segments(arrays)), spec)


def pool_to_device(pool):
    """Pin the in-loop CNF pool (inloop_solve.InloopPool) on device once
    per super-round: the pool rides every fused dispatch as a kernel
    argument, and without an explicit device_put each dispatch would
    re-stage the five host-built arrays over the wire. The pool is tiny
    (a few KB), but the transfer sits on the dispatch critical path —
    the exact seam this tier exists to keep empty."""
    faults.fire(faults.TRANSFER_UP, context="pool_to_device")
    return jax.device_put(pool)


def batch_to_device(np_batch: dict, cfg) -> StateBatch:
    """Host plane dict -> device StateBatch via one upload.

    Plane groups with no content (no symbolic layer, no memory writes,
    no storage) are skipped and rebuilt as zeros on device, and the
    term-tape planes upload only their used row prefix — a freshly
    packed batch is mostly zeros, so the wire payload is typically a few
    hundred KB instead of the full batch.
    """
    faults.fire(faults.TRANSFER_UP, context="batch_to_device")
    shapes = batch_shapes(cfg)
    if monomorphic():
        t_used = cfg.tape_slots
        absent = ()
    else:
        t_used = _bucket(int(np_batch["tape_len"].max()), cfg.tape_slots)
        absent = tuple(
            sorted(
                group
                for group, planes in _UP_GROUPS.items()
                if not any(np_batch[p].any() for p in planes)
            )
        )
    segments = []
    for name in shapes:
        if _GROUP_OF.get(name) in absent:
            continue
        arr = np_batch[name]
        if name in _TAPE_PLANES:
            arr = arr[:, : _tape_cols(name, t_used)]
        segments.append(arr)
    full_key = tuple(
        (name, tuple(shape), np.dtype(dtype).str)
        for name, (shape, dtype) in shapes.items()
    )
    buf = serialize_segments(segments)
    planes = _split_batch(jnp.asarray(buf), full_key, absent, t_used)
    return StateBatch(**dict(zip(shapes.keys(), planes)))


@partial(jax.jit, static_argnames=("full_key", "absent", "t_used"))
def _split_batch(buf, full_key, absent, t_used):
    spec = []
    shipped = []
    for name, full_shape, dtype_str in full_key:
        if _GROUP_OF.get(name) in absent:
            continue
        shape = full_shape
        if name in _TAPE_PLANES:
            shape = (shape[0], _tape_cols(name, t_used)) + tuple(shape[2:])
        spec.append((shape, dtype_str))
        shipped.append(name)
    parts = dict(zip(shipped, split_segments(buf, tuple(spec))))
    out = []
    for name, full_shape, dtype_str in full_key:
        arr = parts.get(name)
        if arr is None:
            dtype = np.dtype(dtype_str)
            zero_dtype = jnp.bool_ if dtype == np.bool_ else jnp.dtype(dtype)
            out.append(jnp.zeros(full_shape, zero_dtype))
            continue
        if tuple(arr.shape) != tuple(full_shape):
            pad = [(0, f - s) for f, s in zip(full_shape, arr.shape)]
            arr = jnp.pad(arr, pad)
        out.append(arr)
    return out


# bulky planes deferred to the second (sized) fetch; everything else is
# small [L]/[L,k] bookkeeping that rides in the first fetch, which also
# carries tape_len so the host can size the tape slice statically
_BIG_DOWN = (
    "stack",
    "stack_sym",
    "memory",
    "visited",
    "calldata",
    "storage_key",
    "storage_val",
    "tape_op",
    "tape_a",
    "tape_b",
    "tape_imm",
    "tape_meta",
)


def _unpack_host(buf: np.ndarray, shapes) -> dict:
    planes = {}
    off = 0
    for name, shape, dtype in shapes:
        nbytes = int(np.prod(shape)) * dtype.itemsize
        planes[name] = buf[off : off + nbytes].view(dtype).reshape(shape)
        off += nbytes
    return planes


def batch_to_host(st: StateBatch, n_shards: int = 1) -> StateBatch:
    """Device StateBatch -> StateBatch of numpy planes in two downloads.

    Fetch 1 moves the small bookkeeping planes (including ``tape_len``);
    fetch 2 moves the bulky planes with the term-tape rows sliced to the
    observed maximum, so a mostly-concrete round moves ~1 MB instead of
    the full batch. ``np.asarray`` on the result's fields is free, so
    everything downstream of a device round (lift/unpack, coverage, step
    counters) reads this view without further transfers.

    ``n_shards > 1`` declares the batch came off the mesh path, where
    compaction is PER SHARD (each contiguous lane block keeps its own
    dense alive prefix) — the bulky planes then ship one lane bucket per
    shard block instead of full height.
    """
    faults.fire(faults.TRANSFER_DOWN, context="batch_to_host")
    small = tuple(
        f
        for f in StateBatch._fields
        if f not in _SKIP_DOWN and f not in _BIG_DOWN
    )
    small_shapes = [
        (f, tuple(getattr(st, f).shape), np.dtype(getattr(st, f).dtype))
        for f in small
    ]
    planes = _unpack_host(np.asarray(_flatten_device(st, small)), small_shapes)

    cap = int(st.tape_op.shape[1])
    L = int(st.alive.shape[0])
    l_used = None
    shard_lanes = None
    if monomorphic():
        t_used = cap
    else:
        t_used = _bucket(int(planes["tape_len"].max()), cap)
        # alive-prefix download: a batch that went through the fused
        # loop's lane compaction (megakernel.compact) keeps its alive
        # frontier as a dense prefix — the bulky planes' dead tail rows
        # are never read by the lift/harvest consumers, so only a lane
        # bucket over the prefix ships. The prefix property is VERIFIED
        # from the already-fetched alive plane (an uncompacted batch —
        # legacy slice loop — simply ships full-height).
        alive = planes["alive"]
        n_alive = int(alive.sum())
        if n_alive < L and not alive[n_alive:].any():
            lb = _lane_bucket(n_alive, L)
            if lb < L:
                l_used = lb
        elif n_shards > 1 and L % n_shards == 0:
            # mesh variant: the shard_map compaction leaves one dense
            # prefix per contiguous shard block; verify each block and
            # ship a common per-shard bucket sized by the fullest shard
            per = L // n_shards
            blocks = alive.reshape(n_shards, per)
            counts = blocks.sum(axis=1)
            dense = all(
                not blocks[s, int(c):].any() for s, c in enumerate(counts)
            )
            if dense:
                lb = _lane_bucket(int(counts.max()), per)
                if lb < per:
                    shard_lanes = (n_shards, lb)
    big_shapes = []
    for f in _BIG_DOWN:
        dev = getattr(st, f)
        shape = tuple(dev.shape)
        if f in _TAPE_PLANES:
            shape = (shape[0], _tape_cols(f, t_used)) + shape[2:]
        if l_used is not None:
            shape = (l_used,) + shape[1:]
        elif shard_lanes is not None:
            shape = (shard_lanes[0] * shard_lanes[1],) + shape[1:]
        big_shapes.append((f, shape, np.dtype(dev.dtype)))
    planes.update(
        _unpack_host(
            np.asarray(
                _flatten_device(st, _BIG_DOWN, t_used, l_used, shard_lanes)
            ),
            big_shapes,
        )
    )
    # pad sliced tape planes back to capacity (rows at or past tape_len
    # are dead by invariant, so zeros are equivalent)
    for f in _TAPE_PLANES:
        if f in planes and planes[f].shape[1] != _tape_cols(f, cap):
            full = np.zeros(
                (planes[f].shape[0], _tape_cols(f, cap)) + planes[f].shape[2:],
                planes[f].dtype,
            )
            full[:, : planes[f].shape[1]] = planes[f]
            planes[f] = full
    # pad lane-sliced planes back to full height (dead-suffix lanes are
    # equivalent to zeros for every host consumer); per-shard buckets go
    # back to their block's original offset
    if l_used is not None:
        for f in _BIG_DOWN:
            if planes[f].shape[0] != L:
                full = np.zeros((L,) + planes[f].shape[1:], planes[f].dtype)
                full[: planes[f].shape[0]] = planes[f]
                planes[f] = full
    elif shard_lanes is not None:
        n, lb = shard_lanes
        per = L // n
        for f in _BIG_DOWN:
            got = planes[f]
            full = np.zeros((L,) + got.shape[1:], got.dtype)
            for s in range(n):
                full[s * per : s * per + lb] = got[s * lb : (s + 1) * lb]
            planes[f] = full
    for name in _SKIP_DOWN:
        dev = getattr(st, name)
        planes[name] = np.zeros(dev.shape, dev.dtype)
    return StateBatch(**planes)


@partial(
    jax.jit, static_argnames=("fields", "t_used", "l_used", "shard_lanes")
)
def _flatten_device(st: StateBatch, fields, t_used=None, l_used=None,
                    shard_lanes=None):
    parts = []
    for name in fields:
        x = getattr(st, name)
        if t_used is not None and name in _TAPE_PLANES:
            x = x[:, : _tape_cols(name, t_used)]
        if l_used is not None:
            x = x[:l_used]
        elif shard_lanes is not None:
            n, lb = shard_lanes
            per = x.shape[0] // n
            x = x.reshape((n, per) + x.shape[1:])[:, :lb].reshape(
                (n * lb,) + x.shape[1:]
            )
        if x.dtype == jnp.bool_:
            x = x.astype(jnp.uint8)
        if x.dtype.itemsize > 1:
            x = jax.lax.bitcast_convert_type(x, jnp.uint8)
        parts.append(x.reshape(-1))
    return jnp.concatenate(parts)
