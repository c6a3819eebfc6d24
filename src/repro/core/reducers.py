"""Allreduce algorithms on manual (shard_map) mesh axes.

This module is the heart of the reproduction: the paper's contribution is
*which algorithm* performs gradient aggregation and *where the reduction
runs*. Each reducer below is an explicit collective algorithm built from
``jax.lax.ppermute`` on a manual mesh axis, so the compiled HLO contains
exactly the communication schedule we wrote — XLA cannot substitute its
own allreduce (that is the ``psum`` baseline, the NCCL2 analogue).

All reducers compute an elementwise SUM over the axis (mean is applied by
the aggregator). They accept arrays of any rank; chunked algorithms chunk
along the leading dimension (padding as needed) so that auto-axis (model
parallel) shardings of trailing dimensions are left undisturbed.

Algorithms
----------
``psum``          XLA-chosen allreduce (vendor-library baseline; NCCL2 analogue)
``ring_rsa``      ring reduce-scatter + ring allgather (Baidu / NCCL ring)
``rhd_rsa``       recursive vector halving/doubling RSA — the paper's
                  proposed MVAPICH2-GDR design (latency-optimal: 2·log2 p
                  steps for power-of-two p; non-pow2 p adds the MVAPICH2
                  pre/post fold, +2 steps and +2·N wire bytes)
``ps_gather``     all-gather + local reduce (parameter-server analogue;
                  ingress is p·N bytes — the PS bottleneck the paper measures)
``hierarchical``  ring reduce-scatter over the intra-pod axis, RHD allreduce
                  over the pod axis, ring allgather back (beyond-paper
                  two-level design for the multi-pod mesh; the pod axis may
                  be any size — 3-, 6-, 12-pod meshes use the non-pow2 path)
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from . import compat
from .compat import all_gather, axis_index, axis_size, ppermute

Axis = str

STRATEGIES = ("psum", "ring_rsa", "rhd_rsa", "ps_gather", "hierarchical")

# Algorithms whose accumulate can route through the fused Pallas hop
# kernel (kernels/fused_hop.py): the ring/RHD hop adds fuse into the
# decode pass, and ps_gather's terminal reduction routes through
# fused_reduce.  psum exposes no hop to fuse (SV009 rejects it) and
# all_gather/shard stages have no accumulate at all.
FUSED_HOP_ALGORITHMS = ("ring_rsa", "rhd_rsa", "ps_gather")


def _as_hop(permute):
    """Adapt a hop primitive to the 4-arg hop protocol
    ``hop(x, axis, perm, add=None)`` — returns ``recv`` (or
    ``add + recv``).  Fused permuters (``codec.permuter(..,
    fused=True)``) advertise ``supports_add`` and fold the add into
    their decode kernel pass; legacy 3-arg permuters get the add
    applied here as a separate op (f32 addition is commutative
    bitwise, so either operand order is bit-identical)."""
    if getattr(permute, "supports_add", False):
        return permute

    def hop(x, axis, perm, add=None):
        r = permute(x, axis, perm)
        return r if add is None else add + r

    return hop


def _pow2_core(p: int) -> int:
    """Largest power of two <= p: the size of the RHD core group."""
    return 1 << (p.bit_length() - 1)


def _pad_leading(x: jax.Array, multiple: int):
    """Pad the leading dim of ``x`` to a multiple of ``multiple``."""
    n = x.shape[0]
    pad = (-n) % multiple
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
    return x, n


def _ring_perm(p: int):
    return [(i, (i + 1) % p) for i in range(p)]


# ---------------------------------------------------------------------------
# psum — vendor baseline
# ---------------------------------------------------------------------------

def psum(x: jax.Array, axis: Axis) -> jax.Array:
    return compat.psum(x, axis)


# ---------------------------------------------------------------------------
# ring reduce-scatter / allgather — composable pieces
# ---------------------------------------------------------------------------

def ring_reduce_scatter(x: jax.Array, axis: Axis, permute=ppermute):
    """Ring reduce-scatter along the leading dim.

    Returns ``(chunk, orig_len)`` where ``chunk`` is this device's fully
    reduced 1/p-th of the (padded) input: device ``i`` owns chunk
    ``(i + 1) % p``.  p-1 steps, each moving N/p bytes.

    ``permute`` is the hop primitive — ``compat.ppermute`` by default, or
    a ``codec.permuter(...)`` wrapper that encodes the payload for the
    wire and decodes on receipt (the adds below stay in the buffer
    dtype, so accumulation precision is untouched by the codec).
    """
    p = axis_size(axis)
    x, n = _pad_leading(x, p)
    if p == 1:
        return x, n
    idx = axis_index(axis)
    perm = _ring_perm(p)
    hop = _as_hop(permute)
    # Chunk i lives at offset i*chunk_len of the padded buffer; a
    # dynamic slice (not jnp.take's gather lowering) fetches it, and
    # the mod-p index is already in range so no wrap handling is
    # needed.
    chunk_len = x.shape[0] // p

    def chunk_at(i):
        return lax.dynamic_slice_in_dim(x, i * chunk_len, chunk_len,
                                        axis=0)

    # Start with our own chunk `idx`; after step s we hold the partial sum
    # of chunk (idx - s) over devices {idx-s, ..., idx}.
    buf = chunk_at(idx)
    for s in range(1, p):
        buf = hop(buf, axis, perm, add=chunk_at((idx - s) % p))
    return buf, n


def ring_all_gather(chunk: jax.Array, axis: Axis, orig_len: int,
                    permute=ppermute):
    """Inverse of ``ring_reduce_scatter``: ring allgather of per-device
    chunks (device ``i`` holding chunk ``(i+1) % p``) back to the full
    leading dim, truncated to ``orig_len``."""
    p = axis_size(axis)
    if p == 1:
        return chunk[:orig_len]
    idx = axis_index(axis)
    perm = _ring_perm(p)
    out = jnp.zeros((p,) + chunk.shape, chunk.dtype)
    cur = chunk
    # After s forwarding steps we hold the chunk owned by device (idx - s),
    # i.e. chunk index (idx - s + 1) % p.
    for s in range(p):
        out = lax.dynamic_update_slice_in_dim(
            out, cur[None], (idx - s + 1) % p, axis=0)
        if s != p - 1:
            cur = permute(cur, axis, perm)
    out = out.reshape(p * chunk.shape[0], *chunk.shape[1:])
    return out[:orig_len]


def ring_rsa(x: jax.Array, axis: Axis, permute=ppermute) -> jax.Array:
    """Bandwidth-optimal ring allreduce (Baidu/NCCL): 2(p-1) steps,
    2N(p-1)/p bytes on the wire per device."""
    chunk, n = ring_reduce_scatter(x, axis, permute=permute)
    return ring_all_gather(chunk, axis, n, permute=permute)


# ---------------------------------------------------------------------------
# recursive vector halving/doubling RSA — the paper's proposed design
# ---------------------------------------------------------------------------

def rhd_rsa(x: jax.Array, axis: Axis, permute=ppermute) -> jax.Array:
    """Recursive vector halving & doubling reduce-scatter/allgather
    (Thakur et al. [41]; the algorithm behind the paper's MVAPICH2-GDR
    MPI_Allreduce). 2·log2(p) steps, 2N(p-1)/p bytes — latency-optimal
    for power-of-two p.

    Non-power-of-two p uses MVAPICH2's pre/post handling: with
    ``core = 2^⌊log2 p⌋`` and ``r = p - core`` excess ranks, excess rank
    ``core + j`` folds its buffer into core rank ``j`` (pre-processing,
    +1 step, +N bytes), the core runs the pow2 RHD schedule, and core
    rank ``j`` broadcasts the result back to rank ``core + j``
    (post-processing, +1 step, +N bytes).  All phases are static
    ``ppermute`` schedules, so the compiled HLO is exactly this
    communication pattern — no silent ``ring_rsa`` fallback (deviation
    D2 in DESIGN.md is removed).
    """
    p = axis_size(axis)
    if p == 1:
        return x
    core = _pow2_core(p)
    r = p - core
    x, n = _pad_leading(x, core)
    idx = axis_index(axis)
    hop = _as_hop(permute)

    if r:
        # Pre-processing fold: excess rank core+j ships its whole buffer
        # to core rank j.  Non-targets of a ppermute receive zeros, so a
        # single add applies the fold only where it landed.
        pre = [(core + j, j) for j in range(r)]
        x = hop(x, axis, pre, add=x)

    # Reduce-scatter by recursive halving over the core: exchange with
    # partner idx^mask, mask = core/2, ..., 1. Bit clear -> keep lower
    # half, send upper.  Excess ranks take no part (their perms exclude
    # them; they receive zeros and their buffer halves along harmlessly —
    # the post broadcast overwrites whatever they hold).
    buf = x
    mask = core // 2
    while mask >= 1:
        perm = [(i, i ^ mask) for i in range(core)]
        half = buf.shape[0] // 2
        lower, upper = buf[:half], buf[half:]
        bit = (idx & mask) != 0
        send = jnp.where(bit, lower, upper)
        keep = jnp.where(bit, upper, lower)
        buf = hop(send, axis, perm, add=keep)
        mask //= 2
    # Core device idx now owns the fully reduced chunk at offset
    # idx * (N/core).

    # Allgather by recursive doubling, reversing the halving order.
    mask = 1
    while mask < core:
        perm = [(i, i ^ mask) for i in range(core)]
        recv = permute(buf, axis, perm)
        bit = (idx & mask) != 0
        # If our bit is set we hold the upper adjacent block.
        buf = jnp.where(bit,
                        jnp.concatenate([recv, buf], axis=0),
                        jnp.concatenate([buf, recv], axis=0))
        mask *= 2

    if r:
        # Post-processing broadcast: core rank j returns the full result
        # to excess rank core+j, which replaces its (garbage) buffer.
        post = [(j, core + j) for j in range(r)]
        recv = permute(buf, axis, post)
        buf = jnp.where(idx >= core, recv, buf)
    return buf[:n]


# ---------------------------------------------------------------------------
# parameter-server analogue
# ---------------------------------------------------------------------------

def ps_gather(x: jax.Array, axis: Axis, *, fused: bool = False) -> jax.Array:
    """Parameter-server communication pattern: every worker ships its full
    gradient (all-gather, p·N ingress bytes per device) and the reduction
    happens centrally. Reproduces *why* the paper's gRPC PS baseline loses
    at scale; the cost model charges the PS ingress bottleneck.

    ``fused=True`` routes the terminal reduction through the
    ``kernels.fused_reduce`` Pallas kernel (one VMEM-tiled fp32 pass —
    the paper's C2 reduction kernel) instead of the staged ``jnp.sum``;
    for float32 payloads the two are bit-identical."""
    gathered = all_gather(x, axis)          # (p, ...)
    if fused:
        from ..kernels.fused_reduce import fused_reduce as _fused_reduce
        p = gathered.shape[0]
        out = _fused_reduce(gathered.reshape(p, -1), out_dtype=x.dtype)
        return out.reshape(x.shape)
    return jnp.sum(gathered, axis=0)


# ---------------------------------------------------------------------------
# hierarchical two-level reducer (beyond-paper, multi-pod)
# ---------------------------------------------------------------------------

def hierarchical(x: jax.Array, data_axis: Axis, pod_axis: Axis) -> jax.Array:
    """Two-level allreduce for the multi-pod mesh: ring reduce-scatter
    inside the pod (cheap ICI), RHD allreduce of the 1/d-sized shard across
    pods (expensive cross-pod links carry only N/d bytes instead of N),
    ring allgather back inside the pod.  Analogue of the paper's
    intra-node(NVLink)/inter-node(IB) hierarchy.  The pod axis may be
    any size: non-pow2 pod counts route through ``rhd_rsa``'s
    MVAPICH2-style pre/post fold rather than silently degrading."""
    chunk, n = ring_reduce_scatter(x, data_axis)
    chunk = rhd_rsa(chunk, pod_axis)
    return ring_all_gather(chunk, data_axis, n)


# ---------------------------------------------------------------------------
# stage executor (ReduceSchedule decomposition trees, core/schedule.py)
# ---------------------------------------------------------------------------

def _stage_permute(st):
    """The hop primitive for one stage: plain ``ppermute`` for uncoded
    stages, a ``codec.permuter`` encode/decode wrapper when the stage
    carries a wire codec (core/codec.py).  Codecs are only legal on
    algorithms whose hops are explicit ppermutes (the static verifier's
    SV008 rejects the rest before execution; this is the runtime
    backstop).

    A stage flagged ``fused_hop`` gets the FUSED permuter: the hop's
    decode and accumulate (and for coded stages the encode) run as
    single Pallas kernel passes (kernels/fused_hop.py) instead of
    staged XLA ops — the paper's GDR-Opt kernel.  Only
    ``FUSED_HOP_ALGORITHMS`` expose a fusable accumulate (SV009 is the
    static twin of this runtime check)."""
    cname = getattr(st, "codec", "none") or "none"
    fused = bool(getattr(st, "fused_hop", False))
    if fused and st.algorithm not in FUSED_HOP_ALGORITHMS:
        raise ValueError(
            f"fused_hop on {st.op}@{st.axis} ({st.algorithm}): only "
            f"{FUSED_HOP_ALGORITHMS} expose a fusable accumulate")
    if cname == "none":
        if fused and st.algorithm in ("ring_rsa", "rhd_rsa"):
            from . import codec as codec_mod
            return codec_mod.permuter("none", fused=True)
        return ppermute
    from . import codec as codec_mod
    if st.algorithm not in codec_mod.CODED_ALGORITHMS:
        raise ValueError(
            f"codec {cname!r} on {st.op}@{st.axis} ({st.algorithm}): only "
            f"{codec_mod.CODED_ALGORITHMS} expose ppermute hop boundaries")
    return codec_mod.permuter(cname, fused=fused)


def _scoped_hops(inner):
    """Wrap a stage's hop primitive so the ``k``-th ppermute hop of the
    stage runs inside ``jax.named_scope("hop[k]")``: the compiled
    program's ops carry ``.../stage[j]/hop[k]`` (metadata only).  For
    codec'd stages ``inner`` is the encode→permute→decode wrapper, so
    the hop scope covers the codec encode/decode as well."""
    counter = [0]
    inner_hop = _as_hop(inner)

    def permute(x, axis, perm, add=None):
        k = counter[0]
        counter[0] += 1
        with jax.named_scope(f"hop[{k}]"):
            return inner_hop(x, axis, perm, add=add)

    # Keep the hop protocol, so the reducers leave the add to the
    # (possibly fused) inner permuter rather than adding it again.
    permute.supports_add = True
    return permute


def execute_stages(x: jax.Array, stages) -> jax.Array:
    """Run a bucket's decomposition tree (a sequence of
    ``schedule.Stage``-like objects with ``op``/``algorithm``/``axis``)
    against the manual mesh axes.  ``reduce_scatter``/``all_gather``
    pairs nest like parentheses: the gather pops the original length
    recorded by its matching scatter.  This is the ONLY reduction entry
    point of the aggregator — ``hierarchical`` is not a special-cased
    monolith but the stage list ``[reduce_scatter@data, allreduce@pod,
    all_gather@data]``, which is exactly what :func:`hierarchical`
    composes by hand.

    The model bracket's ``shard`` opener (DESIGN.md §3.12) is a local
    slice — pad the leading dim to the model-axis size and keep this
    rank's chunk in the ring RS ownership convention (device i holds
    chunk (i+1) % p) — pushed on the same stack, so its terminal
    ``all_gather`` stage reassembles through :func:`ring_all_gather`
    unchanged.

    Stages carrying a wire codec (``st.codec != "none"``) encode the
    payload around every ppermute hop; the bucket buffer is upcast to
    float32 for the whole stage list (dequantize-reduce-requantize with
    fp32 accumulation, DESIGN.md §3.10) and cast back to its original
    dtype at the end."""
    coded = any((getattr(st, "codec", "none") or "none") != "none"
                for st in stages)
    orig_dtype = x.dtype
    if coded and x.dtype != jnp.float32:
        x = x.astype(jnp.float32)
    pending: list = []                      # (axis, orig_len) stack
    for j, st in enumerate(stages):
        permute = _stage_permute(st)
        # Only ppermute-hop algorithms take a permute override
        # (psum/ps_gather have no explicit hops to scope).
        if st.op != "allreduce" or st.algorithm in ("ring_rsa", "rhd_rsa"):
            permute = _scoped_hops(permute)
        # Under the aggregator's ``bucket[i]`` scope this names the IR
        # path ``bucket[i].stage[j]`` in the compiled program.
        with jax.named_scope(f"stage[{j}]"):
            if st.op == "reduce_scatter":
                if st.algorithm != "ring_rsa":
                    raise ValueError(f"unknown reduce-scatter algorithm "
                                     f"{st.algorithm!r}")
                x, n = ring_reduce_scatter(x, st.axis, permute=permute)
                pending.append((st.axis, n))
            elif st.op == "shard":
                p = axis_size(st.axis)
                x, n = _pad_leading(x, p)
                idx = axis_index(st.axis)
                chunk_len = x.shape[0] // p
                x = lax.dynamic_slice_in_dim(
                    x, ((idx + 1) % p) * chunk_len, chunk_len, axis=0)
                pending.append((st.axis, n))
            elif st.op == "all_gather":
                if not pending or pending[-1][0] != st.axis:
                    raise ValueError(
                        f"all_gather@{st.axis} without a matching "
                        f"reduce_scatter (pending {pending})")
                _, n = pending.pop()
                x = ring_all_gather(x, st.axis, n, permute=permute)
            elif st.op == "allreduce":
                fn = _FLAT_FNS.get(st.algorithm)
                if fn is None:
                    raise ValueError(f"unknown allreduce algorithm "
                                     f"{st.algorithm!r}")
                if st.algorithm == "ps_gather":
                    # No ppermute hops to wrap; fused_hop routes the
                    # terminal reduction through the Pallas kernel.
                    x = fn(x, st.axis,
                           fused=bool(getattr(st, "fused_hop", False)))
                elif permute is not ppermute:
                    x = fn(x, st.axis, permute=permute)
                else:
                    x = fn(x, st.axis)
            else:
                raise ValueError(f"unknown stage op {st.op!r}")
    if pending:
        raise ValueError(f"unterminated reduce_scatter stages: {pending}")
    if coded and x.dtype != orig_dtype:
        x = x.astype(orig_dtype)
    return x


# ---------------------------------------------------------------------------
# public dispatch
# ---------------------------------------------------------------------------

def allreduce(x: jax.Array, axes: Sequence[Axis], strategy: str) -> jax.Array:
    """Sum-allreduce ``x`` over the manual mesh ``axes`` using ``strategy``.

    For multi-axis (multi-pod) meshes, flat strategies fold over the axes
    innermost-first (full allreduce per axis); ``hierarchical`` composes
    reduce-scatter/allgather across the two levels and is the recommended
    multi-pod strategy.
    """
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown strategy {strategy!r}; one of {STRATEGIES}")
    axes = tuple(axes)
    if strategy == "hierarchical":
        if len(axes) == 1:
            # Degenerates to ring on a single-level mesh.
            return ring_rsa(x, axes[0])
        if len(axes) != 2:
            raise ValueError("hierarchical expects (pod_axis, data_axis)")
        pod_axis, data_axis = axes
        return hierarchical(x, data_axis=data_axis, pod_axis=pod_axis)
    fn: Callable = _FLAT_FNS[strategy]
    # Innermost (fastest, intra-pod) axis first.
    for ax in reversed(axes):
        x = fn(x, ax)
    return x


# Flat per-axis allreduce dispatch, shared by ``allreduce`` and the
# stage executor above.
_FLAT_FNS = {"psum": psum, "ring_rsa": ring_rsa,
             "rhd_rsa": rhd_rsa, "ps_gather": ps_gather}


def hierarchical_wire_bytes(n_bytes: int, d: int, pods: int) -> dict:
    """Per-level wire bytes of the two-level schedule, on the busiest
    device: ``intra`` = ring reduce-scatter + ring allgather over the
    d-way pod-local axis (each moves N(d-1)/d bytes), ``inter`` = RHD
    allreduce of the 1/d-sized chunk across ``pods`` (non-pow2 pod
    counts pay the MVAPICH2 pre/post fold on the chunk).  The two levels
    ride different links (ICI vs DCN), which is why the accounting is
    kept split instead of collapsed into one number."""
    if d == 1:
        return {"intra": 0, "inter": wire_bytes("rhd_rsa", n_bytes, pods)}
    intra = 2 * int(n_bytes * (d - 1) / d)
    inter = wire_bytes("rhd_rsa", n_bytes // d, pods)
    return {"intra": intra, "inter": inter}


def _axis_sizes(p) -> tuple[int, ...]:
    """Normalize a device count (int) or per-axis sizes (outermost/pod
    axis first, matching ``allreduce``'s ``axes``) to a tuple."""
    if isinstance(p, int):
        return (p,)
    sizes = tuple(int(s) for s in p)
    if not sizes or any(s < 1 for s in sizes):
        raise ValueError(f"axis sizes must be positive ints, got {p!r}")
    return sizes


def wire_bytes(strategy: str, n_bytes: int, p) -> int:
    """Algorithmic wire bytes per device (critical path) for an
    allreduce of ``n_bytes`` with ``strategy`` (used by the cost model
    and tests).  ``p`` is a device count for a single-axis reduction, or
    per-axis sizes ``(pods, d)`` (outermost first, matching
    ``allreduce``'s ``axes``) for a multi-axis mesh.

    Flat strategies on a multi-axis mesh fold a FULL N-byte allreduce
    over each axis (exactly what ``allreduce`` executes), so their total
    is the per-axis sum.  ``hierarchical`` charges its per-level
    schedule (see :func:`hierarchical_wire_bytes`); on a single axis it
    degenerates to ring, like the executed reducer.

    For non-pow2 ``rhd_rsa`` the busiest device is a core rank paired
    with an excess rank: it receives the N-byte pre-fold, runs the pow2
    core schedule on ``core = 2^⌊log2 p⌋`` ranks, and sends the N-byte
    post broadcast — the MVAPICH2 +2·N pre/post overhead.
    """
    sizes = _axis_sizes(p)
    if strategy == "hierarchical":
        if len(sizes) == 1:
            return wire_bytes("ring_rsa", n_bytes, sizes[0])
        if len(sizes) != 2:
            raise ValueError("hierarchical expects (pods, d) axis sizes")
        pods, d = sizes
        levels = hierarchical_wire_bytes(n_bytes, d=d, pods=pods)
        return levels["intra"] + levels["inter"]
    if len(sizes) > 1:
        return sum(wire_bytes(strategy, n_bytes, s) for s in sizes)
    (p,) = sizes
    if p == 1:
        return 0
    if strategy == "rhd_rsa":
        core = _pow2_core(p)
        extra = 0 if core == p else 2 * n_bytes
        return int(2 * n_bytes * (core - 1) / core) + extra
    if strategy in ("ring_rsa", "psum"):
        return int(2 * n_bytes * (p - 1) / p)
    if strategy == "ps_gather":
        return int(n_bytes * (p - 1))  # recv-dominated
    raise ValueError(strategy)


def allreduce_steps(strategy: str, p) -> int:
    """Number of sequential communication steps (alpha terms) on the
    critical path of an allreduce over ``p`` devices (int) or per-axis
    sizes (outermost first; flat strategies sum per-axis full
    reductions, ``hierarchical`` charges ring-RS + RHD + ring-AG)."""
    sizes = _axis_sizes(p)
    if strategy == "hierarchical":
        if len(sizes) == 1:
            return allreduce_steps("ring_rsa", sizes[0])
        if len(sizes) != 2:
            raise ValueError("hierarchical expects (pods, d) axis sizes")
        pods, d = sizes
        intra = 2 * (d - 1)              # ring RS + ring AG
        return intra + allreduce_steps("rhd_rsa", pods)
    if len(sizes) > 1:
        return sum(allreduce_steps(strategy, s) for s in sizes)
    (p,) = sizes
    if p == 1:
        return 0
    if strategy == "rhd_rsa":
        core = _pow2_core(p)
        pre_post = 0 if core == p else 2
        return 2 * core.bit_length() - 2 + pre_post  # 2*log2(core) (+2)
    if strategy == "ring_rsa":
        return 2 * (p - 1)
    if strategy == "ps_gather":
        return 2                          # push all, pull all
    if strategy == "psum":
        raise ValueError("psum steps are vendor-chosen; use cost_model")
    raise ValueError(strategy)
