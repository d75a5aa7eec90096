"""Algorithm 1 — distributed TRON for formulation (4) — mapped to JAX.

Paper (Hadoop/AllReduce-tree)          ->  this module (TPU mesh)
-------------------------------------------------------------------------
step 1  rows of T scattered to p nodes ->  X, y sharded over the data axes
step 2  basis points broadcast         ->  basis replicated (P())
step 3  node-local row block of C      ->  C sharded P(data_axes, model_axis)
step 4  f/g/Hd = local matvec + AllReduce
                                       ->  shard_map body + lax.psum
The paper's proposed hyper-node extension ("row partitioning per hyper-node,
column partitioning within") is exactly the optional ``model_axis``: rows of
C over the data axes, columns over the model axis (2-D partition of C and W).

Three execution modes:
  * ``shard_map``  — the faithful Algorithm 1: collectives are explicit
    psums, one per paper AllReduce call.
  * ``auto``       — same math as plain jnp under jit with sharded operands;
    XLA SPMD chooses the collective schedule (used in §Perf to compare
    against the hand-written schedule).
  * ``materialize=False`` — C is never stored: every f/g/Hd recomputes its
    C tiles on the fly (paper §3.1 "kernel caching / compute on the fly",
    adapted to TPU by fusing gram+matvec; optionally the Pallas kmvp kernel).
  * ``materialize=False, fused=True`` — the ``otf_shard`` plan: even the
    per-shard (n/p, m) block is never allocated; C beta, C^T D r, and W
    contractions all go through the fused kmvp path (Pallas VMEM tiles on
    TPU, row-chunked jnp recomputation elsewhere), and each f/g/Hd call
    AllReduces exactly one m-vector of partials.

A fourth, out-of-core regime streams X from a :class:`ChunkSource`
(:meth:`DistributedNystrom.solve_stream`, the ``stream`` plan): f/g/Hd are
*accumulated* chunk by chunk through the same fused kmvp closures — each
chunk is row-sharded over the mesh, evaluated, AllReduced (one m-vector
psum), and discarded, so n can exceed host RAM. This is the paper's actual
deployment shape: Map-Reduce nodes re-reading their disk partition every
iteration.

beta (and CG direction d) are replicated, matching the paper ("beta is
broadcast to all nodes"); every m-vector reduction is a single psum.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.compat import shard_map
# the rank-generic reductions (_colsum, and _ct_v with its XLA-CPU
# transpose-avoidance NOTE) are shared with the local-math module
from repro.core.formulation import _colsum, _ct_v
from repro.core.losses import Loss, get_loss
from repro.core.nystrom import KernelSpec, gram
from repro.core.tron import TronConfig, TronResult, tron, tron_host
from repro.sharding import multihost
from repro.util.retry import RetryPolicy, call_with_retry

#: Transient-read policy for the per-iteration chunk stream. Matches
#: ``repro.data.chunks.READ_RETRY`` (the take_rows/basis path) so the
#: whole stream fit tolerates the same fault budget end to end.
_FEEDER_RETRY = RetryPolicy(max_attempts=3, backoff_s=0.02,
                            max_backoff_s=0.5)


@dataclasses.dataclass(frozen=True)
class DistConfig:
    data_axes: Tuple[str, ...] = ("data",)
    model_axis: Optional[str] = None   # column partition (hyper-node scheme)
    mode: str = "shard_map"            # shard_map | auto
    materialize: bool = True           # store C, or recompute on the fly
    backend: str = "jnp"               # gram backend: jnp | pallas
    fused: bool = False                # materialize=False only: fuse gram into
                                       # the matvec (kmvp) so not even the
                                       # per-shard C block is ever allocated
    block_rows: Optional[int] = None   # fused jnp fallback row-chunk override
    policy: str = "fp32"               # dtype policy name for every gram/kmvp
                                       # in the closures (kernels.policy);
                                       # accumulation and beta stay f32

    def _gram_policy(self):
        """Policy to hand ``nystrom.gram``: None for fp32 keeps the
        materialized paths on their exact pre-policy expression tree."""
        return None if self.policy == "fp32" else self.policy


class StreamClosures(NamedTuple):
    """Host-callable TRON closures over a chunked source, plus the jitted
    per-chunk evaluations for jaxpr introspection: tests trace
    ``fg_chunk(Xc, yc, wc, basis, beta)`` / ``hd_chunk(Xc, D, basis, d)``
    (chunk-global shapes; the shard_map sub-jaxpr is walked with per-shard
    avals) to prove no intermediate reaches chunk_rows x m elements.
    ``feeder`` is the :class:`_ChunkFeeder` driving chunk I/O — benchmarks
    read its ``h2d_bytes`` counter to measure host->device traffic."""
    fgrad: Callable
    hessd: Callable
    fg_chunk: Callable
    hd_chunk: Callable
    chunk_rows: int
    n_chunks: int
    feeder: Any = None


def _dp_index(data_axes):
    """Linearized index of this device along the (possibly nested) data axes."""
    idx = jax.lax.axis_index(data_axes[0])
    for ax in data_axes[1:]:
        idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    return idx


def _psum_dp(x, data_axes):
    return jax.lax.psum(x, data_axes)


# Every closure below is generic over a trailing column axis: beta may be
# (m,) or an (m, K) one-vs-rest block, y correspondingly (n,) or (n, K).

def _upd(buf, val, row0):
    """dynamic_update_slice of a row block at any rank."""
    return jax.lax.dynamic_update_slice(buf, val,
                                        (row0,) + (0,) * (val.ndim - 1))


_DEV_CACHE_BYTES = 256 << 20   # default HBM budget for the stream chunk cache


class _ChunkFeeder:
    """Pipelined host->device chunk delivery for the stream closures.

    PR 3's loop paid three per-chunk, per-evaluation costs that this class
    removes — each one matters because CG makes dozens of Hd calls per TRON
    step, and every call walks the whole source:

    * host padding (``np.concatenate`` for the ragged tail chunk, the
      zero-weight mask for every chunk) was rebuilt per call. Now it is
      built once per chunk and cached; only the padded ragged tail keeps
      its X copy, so the host never accumulates the full-size chunks the
      out-of-core plan exists to avoid holding.
    * every chunk was re-transferred host->device per call. Now up to
      ``cache_chunks`` chunks (default: whatever fits ``_DEV_CACHE_BYTES``)
      stay resident on the mesh across evaluations; with the cache warm
      those chunks cost zero transfer.
    * uncached chunks were read+transferred synchronously, serializing disk
      I/O with compute. Now a daemon thread reads, pads, and ``device_put``s
      ``prefetch`` chunks ahead (double buffering by default), so the next
      chunk's transfer overlaps the current chunk's kmvp work.

    ``h2d_bytes`` counts bytes handed to ``jax.device_put`` so benchmarks
    (and the acceptance test) can observe the transfer reduction directly.
    When ``classes`` is given, integer label chunks are expanded on the
    host into (rows, K) one-vs-rest ±1 targets before transfer.

    Multi-controller: when ``source.process_span`` is set, ``source.chunk``
    yields only this host's block of each global chunk. The feeder then
    pads to the per-host slot (``chunk_rows / num_processes`` rows) and
    assembles the global device chunk from per-process blocks
    (:func:`repro.sharding.multihost.put_row_sharded`) — per-host disk
    reads, host RAM, and h2d transfer all drop to 1/P while the device
    arrays (and thus the compiled closures) stay globally identical.
    """

    def __init__(self, source, chunk_rows: int, dtype, x_sh, y_sh, r_sh,
                 classes=None, cache_chunks: Optional[int] = None,
                 prefetch: int = 2, x_dtype=None):
        self.source = source
        self.cr = int(chunk_rows)
        span = getattr(source, "process_span", None)
        # per-host pad target: this host's slot of a global chunk
        self.pad_rows = self.cr // (span[1] if span else 1)
        self.dtype = np.dtype(dtype)
        # X chunks may transfer at a narrower dtype than targets/masks: a
        # bf16 compute policy halves H2D and cache bytes without touching
        # the ±1 targets or the 0/1 mask (exact at any float width).
        self.x_dtype = self.dtype if x_dtype is None else np.dtype(x_dtype)
        self.x_sh, self.y_sh, self.r_sh = x_sh, y_sh, r_sh
        self.classes = None if classes is None else np.asarray(classes)
        self.prefetch = int(prefetch)
        # resident bytes per cached chunk (host-local): X (pad, d) +
        # targets (pad[, K]) + mask (pad,) — the one-vs-rest expansion
        # widens the target block, so the HBM budget must count K columns
        ncols = 1 if self.classes is None else len(self.classes)
        chunk_bytes = (self.pad_rows * source.d * self.x_dtype.itemsize
                       + self.pad_rows * (ncols + 1) * self.dtype.itemsize)
        if cache_chunks is None:
            cache_chunks = _DEV_CACHE_BYTES // max(chunk_bytes, 1)
        self.cache_chunks = max(0, min(int(cache_chunks), source.n_chunks))
        self._host: dict = {}   # i -> (padded X | None, targets, mask)
        self._dev: dict = {}    # i -> (Xd, yd, wd) resident device arrays
        self.h2d_bytes = 0
        self.read_retries = 0
        self._retry = _FEEDER_RETRY
        self._retry_lock = threading.Lock()

    # ------------------------------------------------------------ checkpoint
    def state(self) -> dict:
        """Cursor/identity state for an in-training checkpoint.

        Snapshots land *between* TRON iterations — between complete passes
        over the source — so the cursor proper is always at chunk 0; what
        must survive is the chunk layout identity (to validate the resumed
        source and allow elastic re-rounding) and the transfer accounting.
        """
        return {"n": int(self.source.n), "d": int(self.source.d),
                "chunk_rows": int(self.cr),
                "n_chunks": int(self.source.n_chunks),
                "h2d_bytes": int(self.h2d_bytes),
                "read_retries": int(self.read_retries),
                "classes": None if self.classes is None
                else np.asarray(self.classes).tolist()}

    def restore_state(self, state: dict) -> None:
        """Adopt a checkpointed cursor state (resume).

        The dataset identity (n, d) must match; ``chunk_rows`` may differ —
        elastic restore re-rounds the chunk size to the new mesh's data
        extent, which only re-slices the rows-only partition."""
        n, d = int(state.get("n", -1)), int(state.get("d", -1))
        if (n, d) != (int(self.source.n), int(self.source.d)):
            raise ValueError(
                f"checkpointed stream source was n={n} d={d}; the resumed "
                f"source is n={self.source.n} d={self.source.d} — resume "
                f"must re-read the same dataset")
        self.h2d_bytes = int(state.get("h2d_bytes", 0))
        self.read_retries = int(state.get("read_retries", 0))

    def _targets(self, yc):
        if self.classes is None:
            return np.asarray(yc, self.dtype)
        from repro.data.chunks import ovr_targets
        return ovr_targets(yc, self.classes, dtype=self.dtype)

    def _read_chunk(self, i):
        """One chunk read, retried per ``_FEEDER_RETRY`` — transient disk
        faults below the cap re-read identical bytes, so the training
        trajectory is bit-for-bit unaffected. Retries are counted (they
        run on the prefetch thread too, hence the lock)."""
        def _count(attempt, exc, delay_s):
            with self._retry_lock:
                self.read_retries += 1
        return call_with_retry(self._retry, self.source.chunk, i,
                               label=f"stream-chunk-{i}", on_retry=_count)

    def _host_chunk(self, i):
        hit = self._host.get(i)
        if hit is not None:
            Xc, yc, wc = hit
            if Xc is None:                     # full chunk: re-read, no pad
                Xc = np.asarray(self._read_chunk(i)[0], self.x_dtype)
            return Xc, yc, wc
        Xc, yc = self._read_chunk(i)
        rows = Xc.shape[0]
        pad = self.pad_rows
        Xc = np.asarray(Xc, self.x_dtype).reshape(rows, self.source.d)
        if rows != pad:
            Xc = np.concatenate(
                [Xc, np.zeros((pad - rows, self.source.d), self.x_dtype)])
            yc = np.concatenate(
                [np.asarray(yc), np.zeros((pad - rows,),
                                          np.asarray(yc).dtype)])
        yc = self._targets(yc)
        wc = np.zeros((pad,), self.dtype)
        wc[:rows] = 1.0
        # cache the mask/targets always (O(n) floats total, the same order
        # as y itself) and the padded X only for the ragged tail — caching
        # every X chunk would quietly pull the whole dataset into host RAM
        self._host[i] = (Xc if rows != pad else None, yc, wc)
        return Xc, yc, wc

    def _device_chunk(self, i, need_y: bool):
        hit = self._dev.get(i)
        if hit is not None:
            Xd, yd, wd = hit
            return (Xd, yd, wd) if need_y else Xd
        Xc, yc, wc = self._host_chunk(i)
        # single-process this is a plain device_put; multi-process every
        # host contributes its pad_rows block and receives the global
        # (chunk_rows, ...) array — the compiled closures see identical
        # shapes either way
        Xd = multihost.put_row_sharded(self.x_sh, Xc)
        self.h2d_bytes += Xc.nbytes
        yd = wd = None
        if need_y or i < self.cache_chunks:
            yd = multihost.put_row_sharded(self.y_sh, yc)
            wd = multihost.put_row_sharded(self.r_sh, wc)
            self.h2d_bytes += yc.nbytes + wc.nbytes
        if i < self.cache_chunks:
            self._dev[i] = (Xd, yd, wd)
        return (Xd, yd, wd) if need_y else Xd

    def chunks(self, need_y: bool = True):
        """Yield device chunks in order: (Xd, yd, wd) triples, or bare Xd
        when ``need_y`` is False (the Hd path bakes the example mask into
        the Gauss-Newton diagonal, so y/w transfers would be dead traffic).
        """
        idxs = range(self.source.n_chunks)
        if self.prefetch <= 1:
            for i in idxs:
                yield self._device_chunk(i, need_y)
            return
        yield from self._prefetched(idxs, need_y)

    def _prefetched(self, idxs, need_y: bool):
        q: queue.Queue = queue.Queue(maxsize=self.prefetch)
        stop = threading.Event()
        end = object()

        def work():
            try:
                for i in idxs:
                    if stop.is_set():
                        return
                    q.put((None, self._device_chunk(i, need_y)))
            except BaseException as e:     # re-raised on the consumer side
                q.put((e, None))
                return
            q.put((None, end))

        t = threading.Thread(target=work, daemon=True,
                             name="stream-chunk-prefetch")
        t.start()
        try:
            while True:
                err, item = q.get()
                if err is not None:
                    raise err
                if item is end:
                    break
                yield item
        finally:
            stop.set()
            while t.is_alive():            # drain so a blocked put can exit
                try:
                    q.get(timeout=0.05)
                except queue.Empty:
                    pass
            t.join()


class DistributedNystrom:
    """Distributed solver for formulation (4) on a device mesh."""

    def __init__(self, mesh: Mesh, lam: float, loss: Loss | str,
                 kernel: KernelSpec, dist: DistConfig = DistConfig()):
        self.mesh = mesh
        self.lam = float(lam)
        self.loss = get_loss(loss) if isinstance(loss, str) else loss
        self.kernel = kernel
        self.dist = dist
        da, ma = dist.data_axes, dist.model_axis
        self.row_spec = P(da)                    # y, o, D
        self.x_spec = P(da, None)                # X rows
        self.c_spec = P(da, ma)                  # C 2-D partition
        self.w_spec = P(da, ma)                  # W 2-D partition (row blocks)
        self.rep_spec = P()                      # beta, d, basis

    # ------------------------------------------------------------------ setup
    def shardings(self):
        ns = lambda spec: NamedSharding(self.mesh, spec)
        return dict(x=ns(self.x_spec), y=ns(self.row_spec), c=ns(self.c_spec),
                    w=ns(self.w_spec), rep=ns(self.rep_spec))

    def precompute(self, X, basis):
        """Steps 2-3: broadcast basis, build sharded C and W.

        Each device builds only its own (C, W) blocks inside shard_map: the
        compiler cannot partition a Pallas gram, and would otherwise gather
        X onto every device and build all of C there."""
        m = basis.shape[0]
        build = shard_map(lambda Xl, b: self._otf_blocks(Xl, b, m),
                          mesh=self.mesh, check_vma=False,
                          in_specs=(self.x_spec, self.rep_spec),
                          out_specs=(self.c_spec, self.w_spec))
        with self.mesh:
            return jax.jit(build)(X, basis)

    # -------------------------------------------------------------- closures
    def _local_fgrad(self, Cb, Wb, yb, beta):
        """Node-local body of paper steps 4a+4b; returns psum-reduced f,g,D.

        Rank-generic: beta (m,) with y (n,) is the paper's binary problem;
        beta (m, K) with y (n, K) evaluates K one-vs-rest columns through
        the same matmuls (f becomes a (K,) vector of per-class objectives).
        """
        da, ma = self.dist.data_axes, self.dist.model_axis
        m_dp = Wb.shape[0]          # W row-block size (m / |data axes|)
        m_mp = Cb.shape[1]          # column-block size (m / |model axis|)

        # column slice of beta this device multiplies against
        if ma is not None:
            col0 = jax.lax.axis_index(ma) * m_mp
        else:
            col0 = 0
        beta_cols = jax.lax.dynamic_slice_in_dim(beta, col0, m_mp, 0)

        o_part = Cb @ beta_cols
        o = jax.lax.psum(o_part, ma) if ma else o_part          # AllReduce (4a)

        Wb_part = Wb @ beta_cols if ma else Wb @ beta
        Wbeta_rows = jax.lax.psum(Wb_part, ma) if ma else Wb_part

        row0 = _dp_index(da) * m_dp
        beta_rows = jax.lax.dynamic_slice_in_dim(beta, row0, m_dp, 0)
        reg_part = _colsum(beta_rows * Wbeta_rows)
        loss_part = _colsum(self.loss.value(o, yb))
        # paper step 4a: both sums AllReduced over the data tree in one shot
        reg, lsum = _psum_dp(jnp.stack([reg_part, loss_part]), da)
        f = 0.5 * self.lam * reg + lsum

        r = self.loss.grad(o, yb)
        g_loss_part = _ct_v(Cb, r)                               # (m_mp[, K])
        g_reg_rows = self.lam * Wbeta_rows                       # (m_dp[, K])
        g_local = _upd(jnp.zeros(beta.shape, beta.dtype), g_reg_rows, row0)
        g_loss = _upd(jnp.zeros(beta.shape, beta.dtype),
                      g_loss_part.astype(beta.dtype), col0)
        # NOTE: g_loss contributions overlap across data shards -> psum over
        # all axes gives the complete gradient (AllReduce 4b).
        g = _psum_dp(g_local, da) + jax.lax.psum(
            _psum_dp(g_loss, da), ma) if ma else _psum_dp(g_local + g_loss, da)

        D = self.loss.diag(o, yb)
        return f, g, D

    def _local_hessd(self, Cb, Wb, Db, d):
        """Node-local body of paper step 4c (gradient path with y=0, D fixed).

        Rank-generic like :meth:`_local_fgrad`; Db is (n,) or (n, K)."""
        da, ma = self.dist.data_axes, self.dist.model_axis
        m_dp = Wb.shape[0]
        m_mp = Cb.shape[1]
        col0 = jax.lax.axis_index(ma) * m_mp if ma else 0
        d_cols = jax.lax.dynamic_slice_in_dim(d, col0, m_mp, 0)

        o_part = Cb @ d_cols
        o = jax.lax.psum(o_part, ma) if ma else o_part           # AllReduce
        Wd_part = Wb @ d_cols if ma else Wb @ d
        Wd_rows = jax.lax.psum(Wd_part, ma) if ma else Wd_part

        row0 = _dp_index(da) * m_dp
        h_loss_part = _ct_v(Cb, Db * o)
        h = _upd(jnp.zeros(d.shape, d.dtype), self.lam * Wd_rows, row0)
        h2 = _upd(jnp.zeros(d.shape, d.dtype),
                  h_loss_part.astype(d.dtype), col0)
        if ma:
            return _psum_dp(h, da) + jax.lax.psum(_psum_dp(h2, da), ma)
        return _psum_dp(h + h2, da)                              # AllReduce

    # ------------------------------------------------- on-the-fly (no C in HBM)
    def _slice_basis(self, basis, m):
        """(row-block for W rows, col-block for C/W cols) of the basis set."""
        da, ma = self.dist.data_axes, self.dist.model_axis
        dp_total = 1
        for ax in da:
            dp_total *= jax.lax.axis_size(ax)
        m_dp = m // dp_total
        row0 = _dp_index(da) * m_dp
        basis_rows = jax.lax.dynamic_slice_in_dim(basis, row0, m_dp, 0)
        if ma is not None:
            m_mp = m // jax.lax.axis_size(ma)
            col0 = jax.lax.axis_index(ma) * m_mp
            basis_cols = jax.lax.dynamic_slice_in_dim(basis, col0, m_mp, 0)
        else:
            basis_cols = basis
        return basis_rows, basis_cols

    def _otf_blocks(self, Xl, basis, m):
        """Recompute this device's C and W blocks in-register (paper §3.1:
        'compute kernel elements on the fly'; TPU version = gram fused into
        the matvec, optionally via the Pallas kmvp kernel)."""
        basis_rows, basis_cols = self._slice_basis(basis, m)
        pol = self.dist._gram_policy()
        Cb = gram(Xl, basis_cols, self.kernel, self.dist.backend, policy=pol)
        Wb = gram(basis_rows, basis_cols, self.kernel, self.dist.backend,
                  policy=pol)
        return Cb, Wb

    def _row_spec_like(self, arr):
        """Row-sharded spec at the rank of ``arr``: (n,) targets y/D/o
        vectors, (n, K) their one-vs-rest column blocks (rows sharded,
        classes replicated)."""
        return self.row_spec if jnp.ndim(arr) == 1 else self.x_spec

    def make_otf_closures(self, X, y, basis):
        """(fgrad, hessd) that never materialize C globally."""
        m = basis.shape[0]
        ysp = self._row_spec_like(y)

        def fg_local(Xl, yb, basis, beta):
            Cb, Wb = self._otf_blocks(Xl, basis, m)
            return self._local_fgrad(Cb, Wb, yb, beta)

        def hd_local(Xl, yb, basis, D, d):
            Cb, Wb = self._otf_blocks(Xl, basis, m)
            del yb
            return self._local_hessd(Cb, Wb, D, d)

        smap = partial(shard_map, mesh=self.mesh, check_vma=False)
        fg_body = smap(fg_local,
                       in_specs=(self.x_spec, ysp, self.rep_spec,
                                 self.rep_spec),
                       out_specs=(self.rep_spec, self.rep_spec, ysp))
        hd_body = smap(hd_local,
                       in_specs=(self.x_spec, ysp, self.rep_spec,
                                 ysp, self.rep_spec),
                       out_specs=self.rep_spec)
        fgrad = lambda beta: fg_body(X, y, basis, beta)
        hessd = lambda D, d: hd_body(X, y, basis, D, d)
        return fgrad, hessd

    # ---------------------------------------- fused on-the-fly (otf_shard)
    def make_fused_closures(self, X, y, basis):
        """(fgrad, hessd) where not even the per-shard C block exists.

        The non-fused on-the-fly path (:meth:`make_otf_closures`) rebuilds
        a full (n/p, m) gram block per evaluation; here every C (and W)
        contraction goes through the fused kmvp path — Pallas VMEM tiles
        on TPU, row-chunked recomputation under the jnp fallback — and the
        only cross-device traffic is one m-vector psum per f/g/Hd call
        (plus a 2-scalar psum for the objective pieces): O(m) bytes,
        O(n m d / p) flops recomputed per evaluation.

        Rows-only partition: the fused kernels contract over full basis
        columns, so a ``model_axis`` column split does not apply here.

        Multi-RHS: with y (n, K) and beta (m, K) every kmvp call below
        contracts all K one-vs-rest columns against the SAME recomputed
        gram tiles — a K-class f/g/Hd costs ~one O(n m d / p) recompute
        pass instead of K, which is the whole point of the multi-RHS
        kernels (kernels/kmvp.py).
        """
        if self.dist.model_axis is not None:
            raise ValueError(
                "fused on-the-fly mode shards rows only (the kmvp kernels "
                "contract over all basis columns in VMEM); use "
                "model_axis=None, or the non-fused materialize=False mode "
                "for the 2-D partition")
        from repro.kernels.ops import otf_kmvp_fwd, otf_kmvp_t
        m = basis.shape[0]
        da = self.dist.data_axes
        ysp = self._row_spec_like(y)
        kw = dict(kind=self.kernel.kind, sigma=self.kernel.sigma,
                  backend=self.dist.backend,
                  block_rows=self.dist.block_rows,
                  policy=self.dist.policy)

        def _w_rows_slice(basis):
            """(row0, basis row-block) this device owns for W contractions."""
            dp_total = 1
            for ax in da:
                dp_total *= jax.lax.axis_size(ax)
            m_dp = m // dp_total
            row0 = _dp_index(da) * m_dp
            return row0, m_dp, jax.lax.dynamic_slice_in_dim(
                basis, row0, m_dp, 0)

        def fg_local(Xl, yl, basis, beta):
            row0, m_dp, basis_rows = _w_rows_slice(basis)
            o = otf_kmvp_fwd(Xl, basis, beta, **kw)               # C_l beta
            Wb_rows = otf_kmvp_fwd(basis_rows, basis, beta, **kw)  # (m_dp[,K])
            beta_rows = jax.lax.dynamic_slice_in_dim(beta, row0, m_dp, 0)
            reg_part = _colsum(beta_rows * Wb_rows)
            loss_part = _colsum(self.loss.value(o, yl))
            reg, lsum = _psum_dp(jnp.stack([reg_part, loss_part]), da)
            f = 0.5 * self.lam * reg + lsum

            r = self.loss.grad(o, yl)
            g_loss = otf_kmvp_t(Xl, basis, r, **kw)               # C_l^T r
            g_local = _upd(jnp.zeros(beta.shape, beta.dtype),
                           self.lam * Wb_rows, row0)
            g = _psum_dp(g_local + g_loss.astype(beta.dtype), da)  # 1 psum
            return f, g, self.loss.diag(o, yl)

        def hd_local(Xl, yl, basis, D, d):
            del yl
            row0, m_dp, basis_rows = _w_rows_slice(basis)
            o = otf_kmvp_fwd(Xl, basis, d, **kw)                  # C_l d
            Wd_rows = otf_kmvp_fwd(basis_rows, basis, d, **kw)
            h_loss = otf_kmvp_t(Xl, basis, D * o, **kw)           # C_l^T(D o)
            h_local = _upd(jnp.zeros(d.shape, d.dtype),
                           self.lam * Wd_rows, row0)
            return _psum_dp(h_local + h_loss.astype(d.dtype), da)  # 1 psum

        smap = partial(shard_map, mesh=self.mesh, check_vma=False)
        fg_body = smap(fg_local,
                       in_specs=(self.x_spec, ysp, self.rep_spec,
                                 self.rep_spec),
                       out_specs=(self.rep_spec, self.rep_spec, ysp))
        hd_body = smap(hd_local,
                       in_specs=(self.x_spec, ysp, self.rep_spec,
                                 ysp, self.rep_spec),
                       out_specs=self.rep_spec)
        fgrad = lambda beta: fg_body(X, y, basis, beta)
        hessd = lambda D, d: hd_body(X, y, basis, D, d)
        return fgrad, hessd

    # ------------------------------------------------- streaming (out of core)
    def make_stream_closures(self, source, basis, classes=None,
                             cache_chunks: Optional[int] = None,
                             prefetch: int = 2) -> "StreamClosures":
        """Accumulator-style (fgrad, hessd) over a chunked dataset.

        Every evaluation walks ``source`` chunk by chunk: the chunk is
        row-sharded over the data axes, pushed through the same fused kmvp
        contractions as :meth:`make_fused_closures`, AllReduced (one
        m-vector psum per chunk), and dropped — so the only X ever on
        device is one ``(chunk_rows, d)`` block (plus the HBM-budgeted
        resident cache below) and no intermediate reaches ``chunk_rows x m``
        elements. Ragged last chunks (and any n not divisible by the data
        extent) are handled with a zero example-weight mask, which is exact
        for every registered loss.

        Chunk I/O is a pipeline (:class:`_ChunkFeeder`): host-side padding
        is cached per chunk, up to ``cache_chunks`` chunks stay resident on
        device across evaluations (CG's Hd calls stop re-transferring the
        dataset), and uncached chunks are prefetched+``device_put`` on a
        background thread, ``prefetch`` deep, overlapping I/O with compute.

        ``classes`` switches the solve to one-vs-rest multi-RHS: the source
        keeps its integer labels, each chunk is expanded on the host into a
        (chunk_rows, K) ±1 target block, and beta/g/Hd are (m, K) — every
        streamed gram recomputation then serves all K classes at once.

        The Gauss-Newton diagonal ``aux`` is one row-sharded
        ``(chunk_rows[, K])`` array per chunk — O(n/p) floats per device
        per class, a factor d/K smaller than the X partition the plan
        refuses to hold. The returned closures are host callables for
        :func:`tron_host`; ``fg_chunk``/``hd_chunk`` are exposed so tests
        can introspect the per-chunk jaxpr and *prove* the memory contract.
        """
        if self.dist.model_axis is not None:
            raise ValueError(
                "streaming mode shards rows only (chunks go through the "
                "fused kmvp path, which contracts over all basis columns); "
                "use model_axis=None")
        from repro.kernels.ops import otf_kmvp_fwd, otf_kmvp_t
        da = self.dist.data_axes
        multihost.check_mesh_spans(self.mesh)
        dp = 1
        for ax in da:
            dp *= self.mesh.shape[ax]
        cr = -(-source.chunk_rows // dp) * dp
        if cr != source.chunk_rows:
            source = source.with_chunk_rows(cr)
        # multi-controller: each process streams only its own partition.
        # A pre-partitioned source (per-host shard dirs) must match the
        # live topology; a shared source is split logically per host.
        span = getattr(source, "process_span", None)
        live = (multihost.process_index(), multihost.process_count())
        if span is not None and span != live:
            raise ValueError(
                f"source is the partition for process {span[0]} of "
                f"{span[1]} but this run is process {live[0]} of "
                f"{live[1]} — open the partition dir matching this "
                f"process (or re-export with save_partition_dirs)")
        if span is None and live[1] > 1:
            from repro.data.chunks import HostPartition
            source = HostPartition(source, *live)
        kw = dict(kind=self.kernel.kind, sigma=self.kernel.sigma,
                  backend=self.dist.backend,
                  block_rows=self.dist.block_rows,
                  policy=self.dist.policy)
        basis_dev = jnp.asarray(basis)
        dtype = np.dtype(source.dtype)
        # X chunks transfer at the policy's compute dtype (bf16 halves H2D
        # bytes); targets, masks, and beta stay at the source/param dtype —
        # the optimizer state is deliberately outside the compute policy.
        from repro.kernels.policy import get_policy
        _pol = get_policy(self.dist.policy)
        x_dtype = dtype if _pol.compute == "float32" else \
            _pol.np_compute_dtype()
        multi = classes is not None

        def fg_chunk(Xl, yl, wl, basis, beta):
            o = otf_kmvp_fwd(Xl, basis, beta, **kw)              # C_chunk beta
            w = wl[:, None] if multi else wl
            lsum = _colsum(w * self.loss.value(o, yl))
            r = w * self.loss.grad(o, yl)
            g = otf_kmvp_t(Xl, basis, r, **kw)                   # C_chunk^T r
            lsum, g = jax.lax.psum((lsum, g.astype(beta.dtype)), da)
            return lsum, g, w * self.loss.diag(o, yl)

        def hd_chunk(Xl, Dl, basis, d):
            o = otf_kmvp_fwd(Xl, basis, d, **kw)                 # C_chunk d
            h = otf_kmvp_t(Xl, basis, Dl * o, **kw)              # C^T (D o)
            return jax.lax.psum(h.astype(d.dtype), da)

        ysp = self.x_spec if multi else self.row_spec            # (cr[, K])
        smap = partial(shard_map, mesh=self.mesh, check_vma=False)
        fg_eval = jax.jit(smap(
            fg_chunk,
            in_specs=(self.x_spec, ysp, self.row_spec,
                      self.rep_spec, self.rep_spec),
            out_specs=(self.rep_spec, self.rep_spec, ysp)))
        hd_eval = jax.jit(smap(
            hd_chunk,
            in_specs=(self.x_spec, ysp, self.rep_spec,
                      self.rep_spec),
            out_specs=self.rep_spec))

        # the lam/2 beta^T W beta term has no X dependence: one fused
        # (m[, K]) contraction per evaluation, replicated on every device
        @jax.jit
        def wv_eval(basis, v):
            return otf_kmvp_fwd(basis, basis, v, **kw)

        feeder = _ChunkFeeder(
            source, cr, dtype,
            x_sh=NamedSharding(self.mesh, self.x_spec),
            y_sh=NamedSharding(self.mesh, ysp),
            r_sh=NamedSharding(self.mesh, self.row_spec),
            classes=classes, cache_chunks=cache_chunks, prefetch=prefetch,
            x_dtype=x_dtype)

        # Multi-controller: every process must hit the wire with the SAME
        # collective sequence. XLA-CPU dispatches independent executions
        # concurrently, so two chunks' psums can interleave differently on
        # different hosts and corrupt the gloo streams (observed as
        # preamble-length aborts). Blocking on each chunk's outputs before
        # launching the next pins the order; single-process runs keep the
        # fully-async pipeline.
        if multihost.active():
            _ordered = jax.block_until_ready
        else:
            _ordered = lambda out: out

        def fgrad(beta):
            beta_h = np.asarray(beta, dtype)
            beta_dev = jnp.asarray(beta_h)
            with self.mesh:
                Wbeta = wv_eval(basis_dev, beta_dev)
                parts, aux = [], []
                for Xc, yc, wc in feeder.chunks(need_y=True):
                    lsum, gc, Dc = _ordered(
                        fg_eval(Xc, yc, wc, basis_dev, beta_dev))
                    parts.append((lsum, gc))
                    aux.append(Dc)
                Wbeta = np.asarray(Wbeta, np.float64)
                f = 0.5 * self.lam * np.sum(
                    beta_h.astype(np.float64) * Wbeta, axis=0)
                g = self.lam * Wbeta
                for lsum, gc in parts:          # host f64 accumulation
                    f = f + np.asarray(lsum, np.float64)
                    g = g + np.asarray(gc, np.float64)
            return f, g.astype(dtype), aux

        def hessd(aux, d):
            d_dev = jnp.asarray(np.asarray(d, dtype))
            with self.mesh:
                Wd = wv_eval(basis_dev, d_dev)
                parts = [_ordered(hd_eval(Xc, Dc, basis_dev, d_dev))
                         for Xc, Dc in zip(feeder.chunks(need_y=False), aux)]
                h = self.lam * np.asarray(Wd, np.float64)
                for hc in parts:
                    h = h + np.asarray(hc, np.float64)
            return h.astype(dtype)

        return StreamClosures(fgrad=fgrad, hessd=hessd,
                              fg_chunk=fg_eval, hd_chunk=hd_eval,
                              chunk_rows=cr, n_chunks=source.n_chunks,
                              feeder=feeder)

    def solve_stream(self, source, basis, beta0=None,
                     cfg: TronConfig = TronConfig(), classes=None,
                     cache_chunks: Optional[int] = None,
                     prefetch: int = 2, checkpoint=None,
                     state0=None) -> TronResult:
        """Out-of-core solve: TRON on the host, f/g/Hd streamed from
        ``source`` (see :meth:`make_stream_closures`). ``classes`` runs a
        one-vs-rest multi-RHS solve: beta is (m, K) and every streamed
        pass over the dataset serves all K classes.

        ``checkpoint`` (a ``repro.checkpoint.TrainingCheckpointer``) gets
        the feeder attached (cursor export into every step file, counter
        restore on resume) and receives a snapshot every ``interval``
        outer iterations; ``state0`` (a ``TronSnapshot``) resumes the
        host loop — valid under ANY data-axis extent, since the snapshot
        holds only replicated m-space state and the chunk size was
        re-rounded to this mesh above."""
        sc = self.make_stream_closures(source, basis, classes=classes,
                                       cache_chunks=cache_chunks,
                                       prefetch=prefetch)
        if checkpoint is not None:
            checkpoint.attach_feeder(sc.feeder)
        if beta0 is None:
            shape = (basis.shape[0],) if classes is None \
                else (basis.shape[0], len(classes))
            beta0 = np.zeros(shape, source.dtype)
        return tron_host(
            sc.fgrad, sc.hessd, beta0, cfg, state0=state0,
            snapshot_every=checkpoint.interval if checkpoint else 0,
            on_snapshot=checkpoint.on_snapshot if checkpoint else None)

    def make_closures(self, C, W, y):
        """(fgrad, hessd) closures over sharded C, W, y for TRON.

        Rank-generic over a trailing class axis on y/beta (one-vs-rest)."""
        if self.dist.mode == "auto":
            # plain global math; XLA SPMD inserts the collectives
            def fgrad(beta, C=C, W=W, y=y):
                o = C @ beta
                Wb = W @ beta
                f = 0.5 * self.lam * _colsum(beta * Wb) \
                    + _colsum(self.loss.value(o, y))
                g = self.lam * Wb + _ct_v(C, self.loss.grad(o, y))
                return f, g, self.loss.diag(o, y)

            def hessd(D, d, C=C, W=W):
                return self.lam * (W @ d) + _ct_v(C, D * (C @ d))

            return fgrad, hessd

        ysp = self._row_spec_like(y)
        smap = partial(shard_map, mesh=self.mesh, check_vma=False)
        fg_body = smap(
            self._local_fgrad,
            in_specs=(self.c_spec, self.w_spec, ysp, self.rep_spec),
            out_specs=(self.rep_spec, self.rep_spec, ysp),
        )
        hd_body = smap(
            self._local_hessd,
            in_specs=(self.c_spec, self.w_spec, ysp, self.rep_spec),
            out_specs=self.rep_spec,
        )
        fgrad = lambda beta: fg_body(C, W, y, beta)
        hessd = lambda D, d: hd_body(C, W, D, d)
        return fgrad, hessd

    # ------------------------------------------------------------------ solve
    def _as_global_rows(self, arr):
        """Row-shard a host array over the spanning mesh (each process
        contributes its contiguous block of rows it already holds in
        full); pass through arrays that are already process-spanning."""
        if isinstance(arr, jax.Array) and not arr.is_fully_addressable:
            return arr
        return multihost.shard_rows_from_replicated(
            np.asarray(arr), self.mesh, self.dist.data_axes)

    def _as_replicated(self, arr):
        if isinstance(arr, jax.Array) and not arr.is_fully_addressable:
            return arr
        return multihost.replicate(np.asarray(arr), self.mesh)

    def solve(self, X, y, basis, beta0=None,
              cfg: TronConfig = TronConfig(), checkpoint=None,
              state0=None) -> TronResult:
        """TRON over the in-memory plan's closures, returned as soon as the
        program is enqueued; one ``estimator.solve`` span (closures, jit
        trace, compile or cache load, ``precompute``)."""
        with obs.span("estimator.solve"):
            if multihost.active():
                # in-memory fit on a process-spanning mesh: X/y become global
                # row-sharded arrays (this process supplies only its block),
                # basis/beta replicas — after which the closures below compile
                # to the exact single-process program, psums included
                multihost.check_mesh_spans(self.mesh)
                X = self._as_global_rows(X)
                y = self._as_global_rows(y)
                basis = self._as_replicated(basis)
                if beta0 is not None:
                    beta0 = self._as_replicated(beta0)
                if not self.dist.fused or self.dist.materialize:
                    raise ValueError(
                        "multi-controller in-memory fits route through the "
                        "fused rows-only closures (plan 'otf_shard'); other "
                        "in-memory plans are rejected at machine construction")
                if checkpoint is not None or state0 is not None:
                    raise ValueError(
                        "checkpointed multi-controller fits use plan 'stream' "
                        "(the paper's deployment shape — tron_host snapshots "
                        "between passes); the in-memory 'otf_shard' traced "
                        "TRON loop cannot hand process-spanning state to the "
                        "host mid-trace")
                if beta0 is None:
                    beta0 = self._as_replicated(
                        np.zeros((basis.shape[0],), np.dtype(X.dtype)))

            if self.dist.materialize:
                C, W = self.precompute(X, basis)
                make, data = self.make_closures, (C, W, y)
            elif self.dist.fused:
                make, data = self.make_fused_closures, (X, y, basis)
            else:
                make, data = self.make_otf_closures, (X, y, basis)
            if beta0 is None:
                beta0 = jnp.zeros((basis.shape[0],), X.dtype)

            if checkpoint is None and state0 is None:
                # the data are arguments, not closure constants: a closed-over
                # array would be baked into the program (X twice on the device,
                # a compile-cache key per dataset), and a process-spanning one
                # may not be closed over at all
                @jax.jit
                def _run(data, beta0):
                    return tron(*make(*data), beta0, cfg)

                with self.mesh:
                    return _run(data, beta0)
            # checkpointed/resumed: tron segments its own jitted while_loop so
            # the host can snapshot between segments (no outer jit here)
            with self.mesh:
                return tron(
                    *make(*data), beta0, cfg, state0=state0,
                    snapshot_every=checkpoint.interval if checkpoint else 0,
                    on_snapshot=checkpoint.on_snapshot if checkpoint else None)
