"""Plan-aware inference engine: the paper's prediction map under every plan.

Training and prediction are the same distributed primitive. The margin
o(x) = k(x, basis)·β is one row of C·β — exactly the row-partitioned
contraction every f/g/Hd evaluation performs — so each execution plan's
``decide`` arm (registered alongside its ``fit`` arm in
:mod:`repro.api.plans`) reuses the plan's training machinery:

* ``local``      — the dense reference: materialize the (n_test, m) test
                   gram on one device, one matmul. Fastest for batches that
                   fit; also the numerical reference every other decide arm
                   is tested against.
* ``shard_map`` / ``auto`` / ``otf`` / ``otf_shard``
                 — rows of the query batch sharded over the mesh's data
                   axes, margins evaluated through the fused/chunked kmvp
                   dispatchers (:func:`repro.kernels.ops.otf_kmvp_fwd`):
                   no (n/p, m) test-gram block ever exists on any device —
                   the same memory contract the training closures keep,
                   asserted by ``repro.core.introspect`` in tests. Margins
                   are row-partitioned like C·β, so prediction needs NO
                   AllReduce — β is broadcast (the paper's step 2) and each
                   device keeps the margins of its own rows. Multiclass
                   (m, K) β blocks ride the multi-RHS kernels: one gram
                   recomputation serves all K columns per batch.
* ``stream``     — out-of-core scoring: the query set lives in a
                   :class:`repro.data.chunks.ChunkSource` (in-memory
                   arrays, or a directory of memory-mapped .npy shards
                   larger than RAM) and margins are produced chunk by
                   chunk through the same ``_ChunkFeeder`` pipeline the
                   training plan uses (background-thread prefetch,
                   host-pad caching). No intermediate reaches
                   chunk_rows × m elements.

Solvers contribute only a :class:`DecisionSpec` — which feature map,
basis points, and weights realize o(x). Nyström solvers (tron,
linearized, ppacksvm) use the identity map with their stored basis; rff
maps x through φ(·) and contracts against an identity basis under a
linear kernel — the same exact reduction its training path uses, so every
plan applies unchanged.
"""
from __future__ import annotations

import math
from pathlib import Path
from typing import Any, Callable, Iterator, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.compat import default_mesh, shard_map
from repro.core.nystrom import KernelSpec, gram
from repro.data.chunks import (ArrayChunkSource, ChunkSource,
                               as_chunk_source)


class DecisionSpec(NamedTuple):
    """How a fitted state realizes the prediction map o(x).

    ``map_x`` is a jit-traceable feature map applied to query rows before
    the kernel contraction (identity for Nyström states, φ(·) for rff);
    ``basis``/``beta`` are the points and weights of o(x) = k(map_x(x),
    basis)·β; ``kernel``/``backend`` parameterize the gram/kmvp calls.
    β may be (m,) or an (m, K) one-vs-rest block — every decide arm is
    rank-generic over the trailing class axis.

    ``identity_basis`` marks the rff-style reduction where the linear
    kernel against an identity basis makes o(x) = map_x(x)·β exactly:
    decide arms then contract the features directly — O(n_q·m·K) instead
    of the O(n_q·m²) identity-gram detour — and never read ``basis``
    (it may be None).

    ``policy`` names the dtype policy (``repro.kernels.policy.POLICIES``)
    every decide arm computes under — solvers populate it from
    ``config.dtype_policy``, so a machine fit (or loaded) with a cheap
    policy serves through it too.
    """
    map_x: Callable
    basis: Any
    beta: Any
    kernel: KernelSpec
    backend: str
    identity_basis: bool = False
    policy: str = "fp32"


def _is_chunked(X) -> bool:
    """Query sets that must route through the stream decide arm."""
    return isinstance(X, (ChunkSource, str, Path))


def as_inference_source(X, config) -> ChunkSource:
    """Coerce a query set into a ChunkSource for chunked scoring.

    Delegates to :func:`repro.data.chunks.as_chunk_source` (same rechunk /
    shard-directory semantics as training) except that plain arrays wrap
    label-less: inference never reads y, so requiring it would be noise.
    """
    if isinstance(X, (ChunkSource, str, Path)):
        return as_chunk_source(X, None, chunk_rows=config.stream.chunk_rows,
                               mmap=config.stream.mmap)
    return ArrayChunkSource(np.asarray(X), None, config.stream.chunk_rows)


def _basis_operand(spec: DecisionSpec):
    """Array to ship as the basis argument of a margin body. Identity-basis
    specs never read it, so a scalar placeholder keeps the body signature
    uniform without materializing an (m, m) eye."""
    if spec.identity_basis:
        return jnp.zeros((), jnp.float32)
    return jnp.asarray(spec.basis)


# ------------------------------------------------------------------- local
def decide_local(config, mesh, spec: DecisionSpec, X, *,
                 backend: Optional[str] = None):
    """Dense single-device reference: materialize the test gram, contract
    (identity-basis specs contract their features directly)."""
    del mesh
    Xe = spec.map_x(jnp.asarray(X))
    if spec.identity_basis:
        return _row_contract(Xe, spec.beta)
    C = gram(Xe, spec.basis, spec.kernel,
             backend if backend is not None else spec.backend,
             policy=spec.policy if spec.policy != "fp32" else None)
    return _row_contract(C, spec.beta)


def _row_contract(A, beta):
    """A·β for A (n, m) and β (m,) or (m, K), as a multiply-and-reduce.

    Not a matmul: XLA:CPU's GEMM/GEMV tiling rounds a row differently
    depending on its position in the batch, so a coalesced serving batch
    would not be bitwise the same rows decided alone. The reduce sums each
    row's m products in one fixed order. On a TPU the f32 products and sums
    run on the vector unit, so no DEFAULT-precision bf16 MXU pass either."""
    beta = jnp.asarray(beta)
    prod = A[..., None] * beta if beta.ndim == 2 else A * beta
    return jnp.sum(prod, axis=1)


# ------------------------------------------------------- fused (on-mesh)
def _resolve_mesh(config, mesh):
    if mesh is not None:
        return mesh
    return default_mesh(config.data_axes, None)


def _data_extent(config, mesh) -> int:
    return math.prod(mesh.shape[a] for a in config.data_axes)


def make_margin_body(config, mesh, spec: DecisionSpec,
                     backend: Optional[str] = None) -> Callable:
    """shard_map body evaluating row-sharded margins through the fused
    kmvp dispatchers — the decide-side sibling of
    ``DistributedNystrom.make_fused_closures``. Rows-only partition;
    margins stay with their rows (no collective). Exposed unjitted so
    tests can trace it and prove the no-(n/p, m) memory contract."""
    from repro.kernels.ops import otf_kmvp_fwd
    da = tuple(config.data_axes)
    kw = dict(kind=spec.kernel.kind, sigma=spec.kernel.sigma,
              backend=backend if backend is not None else spec.backend,
              block_rows=config.otf_block_rows, policy=spec.policy)
    x_spec = P(da, None)
    o_spec = x_spec if jnp.ndim(spec.beta) == 2 else P(da)
    map_x = spec.map_x

    if spec.identity_basis:
        from repro.kernels.policy import get_policy
        precision = get_policy(spec.policy).precision

        def o_local(Xl, basis, beta):
            del basis                      # o = φ(x)·β exactly, no gram
            return jnp.matmul(map_x(Xl), beta, precision=precision)
    else:
        def o_local(Xl, basis, beta):
            return otf_kmvp_fwd(map_x(Xl), basis, beta, **kw)

    return shard_map(o_local, mesh=mesh, check_vma=False,
                     in_specs=(x_spec, P(), P()), out_specs=o_spec)


def decide_fused(config, mesh, spec: DecisionSpec, X, *,
                 backend: Optional[str] = None):
    """Mesh-sharded margins, C never materialized: query rows over the
    data axes, basis/β replicated, per-shard fused kmvp. Any n — ragged
    batches are zero-row padded (padded margins are sliced off, so the
    garbage rows never escape)."""
    mesh = _resolve_mesh(config, mesh)
    dp = _data_extent(config, mesh)
    Xe = jnp.asarray(X)
    n = Xe.shape[0]
    npad = -(-n // dp) * dp
    if npad != n:
        Xe = jnp.pad(Xe, ((0, npad - n), (0, 0)))
    body = make_margin_body(config, mesh, spec, backend)
    with mesh:
        o = body(Xe, _basis_operand(spec), jnp.asarray(spec.beta))
    return o[:n]


# ------------------------------------------------------ stream (out of core)
class StreamDecider(NamedTuple):
    """Chunked margin evaluation over a :class:`ChunkSource`.

    ``o_chunk`` is the jitted per-chunk shard_map body — tests trace it
    to prove no intermediate reaches chunk_rows × m elements. ``margins``
    is a zero-arg callable returning the per-chunk margin iterator
    (np arrays trimmed to true rows). ``feeder`` exposes ``h2d_bytes``
    for transfer accounting."""
    o_chunk: Callable
    chunk_rows: int
    n_chunks: int
    feeder: Any
    source: ChunkSource
    margins: Callable


def make_stream_decider(config, mesh, spec: DecisionSpec,
                        source: ChunkSource, *,
                        backend: Optional[str] = None,
                        cache_chunks: int = 0,
                        prefetch: Optional[int] = None) -> StreamDecider:
    """Build the chunk-by-chunk margin pipeline over ``source``.

    Chunks ride the same :class:`repro.core.distributed._ChunkFeeder`
    the training plan uses — X-only transfers (``need_y=False``),
    background-thread prefetch ``prefetch`` deep (default: the machine's
    ``StreamConfig.prefetch``). The device cache defaults to 0: scoring
    is one pass, so resident chunks would only burn HBM."""
    from repro.core.distributed import _ChunkFeeder
    mesh = _resolve_mesh(config, mesh)
    dp = _data_extent(config, mesh)
    cr = -(-source.chunk_rows // dp) * dp
    if cr != source.chunk_rows:
        source = source.with_chunk_rows(cr)
    body = jax.jit(make_margin_body(config, mesh, spec, backend))
    da = tuple(config.data_axes)
    from repro.kernels.policy import get_policy
    pol = get_policy(spec.policy)
    # Chunks transfer at the policy's compute dtype: under bf16 the feeder
    # halves H2D bytes (and the on-device chunk) before the kernels even run.
    x_dtype = (None if pol.compute == "float32"
               else pol.np_compute_dtype())
    feeder = _ChunkFeeder(
        source, cr, np.dtype(source.dtype), x_dtype=x_dtype,
        x_sh=NamedSharding(mesh, P(da, None)),
        y_sh=NamedSharding(mesh, P(da)),
        r_sh=NamedSharding(mesh, P(da)),
        cache_chunks=cache_chunks,
        prefetch=config.stream.prefetch if prefetch is None else prefetch)
    basis_dev = _basis_operand(spec)
    beta_dev = jnp.asarray(spec.beta)
    n, n_chunks = source.n, source.n_chunks

    def margins() -> Iterator[np.ndarray]:
        with mesh:
            for i, Xd in enumerate(feeder.chunks(need_y=False)):
                rows = min(n - i * cr, cr)
                yield np.asarray(body(Xd, basis_dev, beta_dev))[:rows]

    return StreamDecider(o_chunk=body, chunk_rows=cr, n_chunks=n_chunks,
                         feeder=feeder, source=source, margins=margins)


def decide_stream(config, mesh, spec: DecisionSpec, X, *,
                  backend: Optional[str] = None):
    """Out-of-core margins: accumulate the (n[, K]) output chunk by chunk
    on the host. The only full-size array is the margin vector itself
    (O(n·K) floats — a factor d/K smaller than the X the plan refuses to
    hold); every device intermediate stays under chunk_rows × m. Returns
    a host np.ndarray. For score/predict over sets where even the margin
    vector binds, use the ``KernelMachine.decision_chunks`` /
    ``predict_chunks`` iterators instead."""
    source = as_inference_source(X, config)
    sd = make_stream_decider(config, mesh, spec, source, backend=backend)
    out = None
    at = 0
    for oc in sd.margins():
        if out is None:
            out = np.empty((source.n,) + oc.shape[1:], oc.dtype)
        out[at:at + oc.shape[0]] = oc
        at += oc.shape[0]
    return out


# ------------------------------------------------------- bucketed serving
# Never dispatch a single-row bucket: XLA lowers a (1, d) contraction to a
# different dot/gemm strategy than multi-row shapes, and the one-ULP drift
# that causes would break the continuous-batching determinism contract
# (a row served alone must be bitwise the row served inside a coalesced
# block). Flooring at 2 keeps every bucket in the same gemm family for the
# cost of one padded row on 1-row requests.
MIN_BUCKET = 2


def bucket_rows(n: int, max_batch: int) -> int:
    """Power-of-two batch bucket for ``n`` query rows (floor
    ``MIN_BUCKET``), capped at ``max_batch``. One jit executable per bucket
    instead of one per request size — the standard shape-bucketing trick
    for latency-stable serving."""
    b = MIN_BUCKET
    while b < n:
        b <<= 1
    return min(b, max_batch)


def scatter_rows(margins, sizes) -> list:
    """Split a coalesced margin block back into per-request row slices.

    ``margins`` is the (sum(sizes)[, K]) output of one decide dispatch over
    rows concatenated from many requests; the returned list has one
    (sizes[i][, K]) view per request, in submission order. The inverse of
    the ``np.concatenate`` the batcher performs — together they are the
    continuous-batching contract: one dispatch, many callers, no row ever
    crossing a request boundary."""
    out, at = [], 0
    for s in sizes:
        out.append(margins[at:at + s])
        at += s
    return out


class BucketedDecider:
    """Bucketed jit-executable cache over one plan's decide callable.

    The batch-composable serving primitive: ``__call__`` pads a request (or
    a coalesced multi-request block) up to its power-of-two bucket, runs
    the cached executable for that bucket, and trims the padding rows off —
    so the jit cache holds at most log2(max_batch)+1 executables no matter
    how many distinct batch sizes traffic produces. Oversize inputs split
    into max_batch-row dispatches. Per-row margins are batch-composition
    independent (rows reduce over m only), so a row served inside any
    bucket equals the same row served alone — the property continuous
    batching relies on and tests assert bitwise.
    """

    def __init__(self, decide: Callable, max_batch: int = 256):
        self.max_batch = int(max_batch)
        self._decide = decide
        self._compiled = {}

    def _compiled_for(self, b: int):
        if b not in self._compiled:
            self._compiled[b] = jax.jit(self._decide)
        return self._compiled[b]

    def __call__(self, X) -> np.ndarray:
        """Margins for ``X`` as a host array, synchronously. Padding and
        trimming happen host-side in numpy — only the bucket-shaped
        executable itself touches XLA, so no request size ever triggers an
        eager pad/slice compile (those one-off ~100 ms stalls would
        dominate tail latency). Each call is one ``infer.decide`` span
        (:mod:`repro.obs`): pad, run, copy the margins to the host."""
        with obs.span("infer.decide"):
            X = np.asarray(X)
            n = X.shape[0]
            if n > self.max_batch:      # split oversize (coalesced) blocks
                return np.concatenate(
                    [self._bucketed(X[i:i + self.max_batch])
                     for i in range(0, n, self.max_batch)])
            return self._bucketed(X)

    def _bucketed(self, X: np.ndarray) -> np.ndarray:
        n = X.shape[0]
        b = bucket_rows(n, self.max_batch)
        if b != n:
            Xp = np.zeros((b,) + X.shape[1:], X.dtype)
            Xp[:n] = X
        else:
            Xp = X
        return np.asarray(self._compiled_for(b)(Xp))[:n]

    def padded_rows(self, n: int) -> int:
        """Device rows one ``__call__(n rows)`` dispatches, padding and
        oversize splits included — the denominator of batch occupancy."""
        full, rem = divmod(n, self.max_batch)
        total = full * self.max_batch
        if rem:
            total += bucket_rows(rem, self.max_batch)
        return total

    def warmup(self, d: int, dtype=np.float32) -> int:
        """Precompile every bucket (1, 2, 4, ..., max_batch) for feature
        dimension ``d`` so no live request ever pays a compile. Returns the
        executable count. Reachable buckets are the powers of two from
        ``MIN_BUCKET`` below ``max_batch`` plus ``max_batch`` itself (the
        cap bucket, which need not be a power of two)."""
        b = MIN_BUCKET
        while b < self.max_batch:
            self(np.zeros((b, d), dtype))
            b <<= 1
        self(np.zeros((self.max_batch, d), dtype))
        return self.n_executables

    @property
    def n_executables(self) -> int:
        return len(self._compiled)


def iter_label_chunks(source: ChunkSource, chunk_rows: int) -> Iterator:
    """Re-chunk ``source``'s label stream to exactly ``chunk_rows`` rows
    per block (last block ragged), aligned with a same-sized
    :class:`StreamDecider`. Uses :meth:`ChunkSource.iter_y`, so .npy
    shard dirs read only their y files — no X bytes touched."""
    buf: Optional[np.ndarray] = None
    for seg in source.iter_y():
        seg = np.asarray(seg)
        buf = seg if buf is None or not buf.size else np.concatenate(
            [buf, seg])
        while buf.shape[0] >= chunk_rows:
            yield buf[:chunk_rows]
            buf = buf[chunk_rows:]
    if buf is not None and buf.shape[0]:
        yield buf
