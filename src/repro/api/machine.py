"""`KernelMachine`: the one estimator every entrypoint targets.

    config = MachineConfig(kernel=KernelSpec("gaussian", sigma=2.0),
                           lam=0.5, solver="tron", plan="shard_map")
    km = KernelMachine(config).fit(X, y, basis)
    yhat = km.predict(Xt)
    km.save("machine.npz")
    km2 = KernelMachine.load("machine.npz")

Swapping single-node for distributed training, stage-wise growth, RFF, or
the baselines is a config edit, not a code path change — the paper's
"one objective, many execution strategies" claim made into an API.
"""
from __future__ import annotations

import hashlib
from typing import Callable, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.api.config import MachineConfig
from repro.api.infer import (_is_chunked, as_inference_source,
                             iter_label_chunks, make_stream_decider)
from repro.api.registry import get_plan, get_solver, validate
from repro.api.result import FitResult
from repro.checkpoint import (check_resume_config, load_arrays, load_latest,
                              save_checkpoint)
from repro.core.basis import select_basis
from repro.core.nystrom import build_C, build_W, gram

# solver/plan registration happens on import
import repro.api.plans    # noqa: F401
import repro.api.solvers  # noqa: F401

_CKPT_FORMAT = 1


def _x_fingerprint(X) -> tuple:
    """Cheap dataset identity for the local-plan (C, W) growth cache.

    Shape alone is NOT identity — two same-shape datasets must not share
    cached kernel columns — so the key adds dtype and a strided-sample
    checksum (≤ ~8k elements hashed regardless of n·d: O(1)-ish against
    the O(n·m·d) gram build the cache avoids). Sampling is a deliberate
    tradeoff: a swap to independently-generated data is caught with
    near-certainty, but a surgical in-place edit confined to unsampled
    rows is not — callers who mutate X between grow calls should treat it
    as a new dataset (jax arrays, being immutable, cannot hit this)."""
    n, d = map(int, X.shape)
    sample = np.ascontiguousarray(
        np.asarray(X[:: max(1, n // 64), :: max(1, d // 8)]))
    return (n, d, str(sample.dtype),
            hashlib.sha1(sample.tobytes()).hexdigest())


class KernelMachine:
    """Estimator over formulation (4) with pluggable solver and plan.

    Attributes set by fitting:
      ``state_``    — flat dict of arrays (the deployable model)
      ``history_``  — one :class:`FitResult` per fit/partial_fit call
      ``result_``   — the latest :class:`FitResult`
    """

    def __init__(self, config: MachineConfig = MachineConfig(), *, mesh=None):
        validate(config.solver, config.plan)   # fail at construction, not fit
        self.config = config
        self.mesh = mesh
        self.state_: Optional[dict] = None
        self.history_: List[FitResult] = []
        self._cw = None          # (C, W) cache for local stage-wise growth
        self._cw_key = None      # data fingerprint the cache was built on

    # ------------------------------------------------------------------- fit
    @property
    def result_(self) -> Optional[FitResult]:
        return self.history_[-1] if self.history_ else None

    def fit(self, X, y, basis=None, *, beta0=None, key=None, checkpoint=None):
        """Train from scratch. ``basis`` defaults to ``config.basis_strategy``
        selection of ``config.m`` points (ignored by rff/ppacksvm solvers).

        Integer multiclass y (solver ``tron``) trains one-vs-rest: all K
        beta columns in ONE column-batched TRON pass, sharing every gram
        recomputation under the fused/stream plans. ``decision_function``
        then returns (n, K) margins and :meth:`predict` argmaxes back to
        the original labels.

        ``checkpoint`` (a :class:`repro.checkpoint.CheckpointConfig`,
        solver ``tron`` only) commits preemption-safe in-training step
        files every ``interval`` outer iterations; with
        ``checkpoint.resume=True`` the fit first restores the newest step
        in ``checkpoint.dir`` — including its stored basis (and one-vs-rest
        class order), so the restarted run optimizes the identical
        objective — and continues from that iterate.

        Each call is one ``machine.fit`` span (:mod:`repro.obs`), the
        parent of its ``estimator.solve`` and ``estimator.wait`` spans.
        """
        with obs.span("machine.fit"):
            entry = validate(self.config.solver, self.config.plan)
            resume = None
            if checkpoint is not None:
                if self.config.solver != "tron":
                    raise ValueError(
                        f"in-training checkpoints snapshot TRON iterate "
                        f"state; solver {self.config.solver!r} does not "
                        f"support checkpoint= (use solver='tron')")
                if checkpoint.resume:
                    resume = load_latest(checkpoint.dir)
                    check_resume_config(self.config, resume.meta)
                    if "basis" in resume.arrays:
                        # the stored basis IS the objective's identity:
                        # never re-select (a fresh random draw would change
                        # k(x, basis))
                        basis = jnp.asarray(resume.arrays["basis"])
            if key is None:
                key = jax.random.PRNGKey(self.config.seed)
            if basis is None and entry.needs_basis:
                from repro.data.chunks import (ChunkSource,
                                               random_basis_from_source)
                if isinstance(X, ChunkSource):   # out-of-core: O(m) rows read
                    if self.config.basis_strategy not in ("random", "auto"):
                        raise ValueError(
                            f"basis_strategy {self.config.basis_strategy!r} "
                            f"needs X in memory; chunked sources support "
                            f"'random' (or pass an explicit basis)")
                    basis = jnp.asarray(random_basis_from_source(
                        key, X, self.config.m))
                else:
                    basis = select_basis(key, X, self.config.m,
                                         strategy=self.config.basis_strategy,
                                         mesh=self.mesh,
                                         data_axes=self.config.data_axes)
            hooks = {} if checkpoint is None else {"checkpoint": checkpoint,
                                                   "resume": resume}
            state, res = entry.fit(self.config, X, y, basis, beta0,
                                   mesh=self.mesh, plan=self.config.plan,
                                   key=key, **hooks)
            self.state_ = state
            self.history_ = [res]
            self._cw = self._cw_key = None
            return self

    def partial_fit(self, X, y, new_basis, *, key=None):
        """Stage-wise basis growth (paper §3): add ``new_basis`` points,
        warm-start beta (old coordinates kept, new ones zero) and re-solve.

        Under the ``local`` plan only the NEW columns of C (and new blocks
        of W) are computed — the incrementality the paper highlights as
        formulation (4)'s advantage over (3)'s incremental SVD. Distributed
        plans rebuild their sharded (C, W) but keep the warm start. The
        cache is keyed on a data fingerprint (shape + dtype + sampled
        checksum), so passing *different* data of the same shape rebuilds
        the kernel columns instead of silently reusing stale ones.
        """
        entry = validate(self.config.solver, self.config.plan)
        if not entry.grows:
            raise ValueError(
                f"solver {self.config.solver!r} does not support stage-wise "
                f"basis growth (partial_fit); use solver='tron'")
        new_basis = jnp.asarray(new_basis)
        kern, backend = self.config.kernel, self.config.backend
        local = self.config.plan == "local"
        xkey = _x_fingerprint(X) if local else None   # computed once per call

        if self.state_ is None:
            basis = new_basis
            beta0 = None      # solver picks (m,) or (m, K) zeros to match y
            if local:
                self._cw = (build_C(X, basis, kern, backend),
                            build_W(basis, kern, backend))
                self._cw_key = xkey
        else:
            old_basis, old_beta = self.state_["basis"], self.state_["beta"]
            basis = jnp.concatenate([old_basis, new_basis], axis=0)
            # warm start keeps every old coordinate — including the K
            # one-vs-rest columns of a multiclass beta (rank-generic zeros)
            beta0 = jnp.concatenate(
                [old_beta, jnp.zeros((new_basis.shape[0],)
                                     + old_beta.shape[1:], old_beta.dtype)])
            if local:
                # sampled-checksum comparison, never id(): an id fast path
                # would falsely hit on in-place-mutated numpy arrays and on
                # CPython id reuse
                if self._cw is not None and self._cw_key == xkey:
                    C, W = self._cw          # only new columns/blocks below
                else:                        # fit() first, or swapped data
                    C = build_C(X, old_basis, kern, backend)
                    W = build_W(old_basis, kern, backend)
                C_new = gram(X, new_basis, kern, backend)
                W_cross = gram(old_basis, new_basis, kern, backend)
                W_new = gram(new_basis, new_basis, kern, backend)
                C = jnp.concatenate([C, C_new], axis=1)
                W = jnp.block([[W, W_cross], [W_cross.T, W_new]])
                self._cw = (C, W)
                self._cw_key = xkey

        state, res = entry.fit(self.config, X, y, basis, beta0,
                               mesh=self.mesh, plan=self.config.plan,
                               key=key, CW=self._cw if local else None)
        self.state_ = state
        self.history_.append(res)
        return self

    # --------------------------------------------------------------- predict
    def _require_fitted(self):
        if self.state_ is None:
            raise RuntimeError("KernelMachine is not fitted; call fit() or "
                               "load() first")

    def _decision_plan(self, X, plan: Optional[str]) -> str:
        """Resolve which plan's decide arm serves this query set."""
        if plan is None:
            return "stream" if _is_chunked(X) else self.config.plan
        get_plan(plan)                       # fail fast on unknown names
        if _is_chunked(X) and plan != "stream":
            raise ValueError(
                f"plan {plan!r} scores in-memory batches; a ChunkSource / "
                f"shard-directory query set routes through plan='stream' "
                f"(or use decision_chunks/predict_chunks)")
        return plan

    def _spec(self):
        return get_solver(self.config.solver).decision_spec(self.config,
                                                            self.state_)

    def decision_function(self, X, *, plan: Optional[str] = None,
                          backend: Optional[str] = None):
        """Raw margin o(x) through the execution-plan registry. Shape (n,)
        for a binary machine, (n, K) per-class margins for one-vs-rest.

        ``plan`` overrides the training plan for this evaluation — any
        registered plan is valid for inference regardless of how the
        machine was trained (a ``stream``-trained machine serves small
        batches via ``'local'``; a ``local``-trained machine scores a
        larger-than-RAM shard directory via ``'stream'``). ``X`` may be a
        :class:`~repro.data.chunks.ChunkSource` or shard-directory path
        (routed through ``'stream'``, margins returned as one host
        array); arrays go to the resolved plan's decide arm.
        """
        self._require_fitted()
        plan = self._decision_plan(X, plan)
        return get_plan(plan).decide(self.config, self.mesh, self._spec(),
                                     X, backend=backend)

    def decision_chunks(self, X) -> Iterator:
        """Streaming margins: yield one (rows[, K]) host array per chunk of
        ``X`` (array, ChunkSource, or shard-directory path), evaluated
        through the stream decide pipeline — bounded memory even when the
        full margin vector would not fit."""
        self._require_fitted()
        sd = make_stream_decider(self.config, self.mesh, self._spec(),
                                 as_inference_source(X, self.config))
        return sd.margins()

    def _labels(self, o):
        if "classes" in self.state_:
            return self.state_["classes"][jnp.argmax(jnp.asarray(o), axis=-1)]
        return jnp.sign(jnp.asarray(o))

    def predict(self, X, *, plan: Optional[str] = None):
        """±1 signs for a binary machine; original integer labels (argmax
        over the one-vs-rest margins) for a multiclass machine."""
        return self._labels(self.decision_function(X, plan=plan))

    def predict_chunks(self, X) -> Iterator:
        """Streaming :meth:`predict`: one host label array per chunk."""
        for o in self.decision_chunks(X):
            yield np.asarray(self._labels(o))

    def score(self, X, y=None, *, plan: Optional[str] = None) -> float:
        """Mean accuracy. A chunked ``X`` (ChunkSource / shard directory)
        scores chunk-by-chunk in bounded memory; ``y=None`` then reads the
        labels from the source itself (y-only shard reads)."""
        self._require_fitted()
        if _is_chunked(X):
            self._decision_plan(X, plan)   # reject non-stream overrides
            source = as_inference_source(X, self.config)
            sd = make_stream_decider(self.config, self.mesh, self._spec(),
                                     source)
            labels = iter_label_chunks(sd.source, sd.chunk_rows) \
                if y is None else None
            correct = total = 0
            at = 0
            for o in sd.margins():
                pred = np.asarray(self._labels(o))
                rows = pred.shape[0]
                yc = next(labels) if labels is not None \
                    else np.asarray(y)[at:at + rows]
                correct += int(np.sum(pred == yc))
                total += rows
                at += rows
            return correct / total
        if y is None:
            raise TypeError("score() needs y for in-memory X (only chunked "
                            "sources carry their own labels)")
        # exact-count division (not f32 jnp.mean) so the in-memory and
        # chunked paths return bit-identical accuracies for identical
        # predictions at any n
        pred = np.asarray(self.predict(X, plan=plan))
        return int(np.sum(pred == np.asarray(y))) / pred.shape[0]

    def decider(self, *, plan: Optional[str] = None,
                backend: Optional[str] = None) -> Callable:
        """A stable ``X -> margins`` callable bound to one plan's decide
        arm — what a serving loop jit-compiles per batch bucket
        (:mod:`repro.launch.kernel_serve`). The ``local`` and fused-plan
        deciders are jit-traceable; the ``stream`` decider is host-driven
        (serve a stream-trained machine via ``plan='local'`` or
        ``'otf_shard'`` instead)."""
        self._require_fitted()
        entry = get_plan(plan or self.config.plan)
        config, mesh, spec = self.config, self.mesh, self._spec()

        def decide(X):
            return entry.decide(config, mesh, spec, X, backend=backend)

        return decide

    # ------------------------------------------------------------- save/load
    def save(self, path: str, *, quantize: Optional[str] = None):
        """Persist state + config via repro.checkpoint (single .npz).

        ``quantize="int8"`` stores the heavy state arrays (basis, beta) as
        symmetric per-column int8 codes with fp32 scales — ~4× smaller
        checkpoints for serving fleets (see ``repro.checkpoint.quant``).
        :meth:`load` dequantizes transparently; margins shift by at most
        the per-column rounding step, bounded by the round-trip test."""
        self._require_fitted()
        meta = {"format": _CKPT_FORMAT, "config": self.config.to_dict(),
                "history": [
                    {"solver": r.solver, "plan": r.plan, "m": r.m, "f": r.f,
                     "n_iter": r.n_iter, "converged": r.converged}
                    for r in self.history_]}
        tree = dict(self.state_)
        if quantize is not None:
            from repro.checkpoint.quant import quantize_state
            tree, manifest = quantize_state(tree, quantize)
            meta["quantized"] = manifest
        save_checkpoint(path, tree, metadata=meta)
        return path

    @classmethod
    def load(cls, path: str, *, mesh=None,
             policy: Optional[str] = None) -> "KernelMachine":
        """Restore a machine from :meth:`save` output.

        Pre-policy fp32 checkpoints (no ``dtype_policy`` config key, no
        quantization manifest) load byte-identically under the default
        policy. ``policy`` overrides the checkpointed ``dtype_policy`` for
        this instance — the standard serving move is training fp32 then
        loading with ``policy="bf16"`` (often on a ``quantize="int8"``
        checkpoint) to serve through the cheap decide arm."""
        arrays, meta = load_arrays(path)
        if meta.get("format") != _CKPT_FORMAT:
            raise ValueError(f"{path}: not a KernelMachine checkpoint "
                             f"(format={meta.get('format')!r})")
        if meta.get("quantized"):
            from repro.checkpoint.quant import dequantize_state
            arrays = dequantize_state(arrays, meta["quantized"])
        config = MachineConfig.from_dict(meta["config"])
        if policy is not None:
            config = config.replace(dtype_policy=policy)
        km = cls(config, mesh=mesh)
        km.state_ = {k: jnp.asarray(v) for k, v in arrays.items()}
        return km
