"""Bucketed AOT compilation and batched execution.

Every distinct input shape is a distinct XLA program, so free-form dynamic
batching would recompile constantly (SURVEY §7 hard part 3).  The fix: a fixed
set of (batch[, seq]) buckets per model, each compiled once by tracing the
regular ``jax.jit`` callable on the bucket shape — at boot when
``warmup_at_boot`` is set, else on first use — and requests padded up to the
smallest fitting bucket.  (Not AOT ``lower().compile()`` executables: the jit
path keeps XLA's C++ fast dispatch — see the measured note in
:class:`CompiledModel`.)  The pad rows are real compute wasted to buy shape
stability; buckets grow geometrically so waste is bounded at ~2x worst case
and ~1.3x typical.
"""

from __future__ import annotations

import itertools
from typing import Any, Sequence

import jax
import numpy as np

from ..config import ModelConfig
from .cache import CompileClock
from .servable import Servable


def default_collate(samples: Sequence[dict[str, np.ndarray]], bucket: tuple[int, ...],
                    input_spec: dict[str, jax.ShapeDtypeStruct]) -> dict[str, np.ndarray]:
    """Stack per-sample arrays and zero-pad every axis up to the bucket spec.

    Zero is the pad value on all axes (batch rows, token ids, masks); token
    servables that need a different pad id supply their own collate via
    ``Servable.meta['collate']``.
    """
    from ..ops import hostops

    out = {}
    for key, spec in input_spec.items():
        per_sample = spec.shape[1:]
        arrays = [np.asarray(s[key]) for s in samples]
        if (spec.dtype == np.uint8
                and all(a.shape == per_sample and a.dtype == np.uint8 for a in arrays)):
            # Uniform-shape uint8 (the image-servable case): native batch pack
            # (native/hostops.cpp pack_batch_u8), one memcpy per sample straight
            # into the zero-padded bucket buffer.
            out[key] = hostops.pack_batch_u8(arrays, spec.shape[0])
            continue
        padded = []
        for a in arrays:
            pads = [(0, want - have) for want, have in zip(per_sample, a.shape)]
            padded.append(np.pad(a, pads) if any(p != (0, 0) for p in pads) else a)
        stacked = np.stack(padded).astype(spec.dtype)
        rows = spec.shape[0] - stacked.shape[0]
        if rows:
            stacked = np.pad(stacked, [(0, rows)] + [(0, 0)] * (stacked.ndim - 1))
        assert stacked.shape == spec.shape, (key, stacked.shape, spec.shape)
        out[key] = stacked
    return out


class CompiledModel:
    """One servable + its per-bucket compiled executables.

    With a ``mesh`` (ServeConfig.mesh → engine.loader), serving goes SPMD:
    params are placed by the servable's family TP rules
    (``meta['tp_rules']``, parallel/mesh.py) and XLA's partitioner inserts
    the collectives.  Batch placement is per-bucket: a bucket whose row count
    divides the ``data`` axis shards rows across it (DP); any other bucket
    (e.g. the (1,) bucket of an expensive single-request model like sd15)
    replicates its inputs and serves TP-only — never padding a request up to
    data_par rows just to shard it, which would multiply device time for
    zero extra answers.
    """

    def __init__(self, servable: Servable, cfg: ModelConfig,
                 clock: CompileClock | None = None, mesh=None):
        self.servable = servable
        self.cfg = cfg
        self.clock = clock or CompileClock()
        self.mesh = mesh
        self._data_par = 1
        # QoS class for the priority dispatch lane (engine/runner.py): config
        # override first, then the class the model family registered, then
        # servable meta (direct Servable construction outside the registry).
        from ..utils.registry import LATENCY_CLASSES, get_latency_class

        lc = (cfg.latency_class
              or get_latency_class(getattr(cfg, "builder", "") or cfg.name)
              or servable.meta.get("latency_class") or "latency")
        if lc not in LATENCY_CLASSES:
            raise ValueError(f"{cfg.name}: latency_class must be one of "
                             f"{LATENCY_CLASSES}, got {lc!r}")
        self.latency_class = lc
        params_dtype = cfg.extra.get("params_dtype")
        if str(params_dtype) == "auto":
            # Regime-routed lane (models/gpt2.py): the builder holds BOTH a
            # bf16 and a W8A16 tree and routes per compiled program; the
            # generic at-rest cast must not touch the dual tree.
            params_dtype = None
            if not (isinstance(servable.params, dict)
                    and "bf16" in servable.params
                    and "int8" in servable.params):
                raise ValueError(
                    f"{cfg.name}: params_dtype=auto requested but this model "
                    f"family has no regime-routed lane (builder did not "
                    f"produce the dual bf16/int8 tree); use "
                    f"params_dtype=bfloat16 or int8")
            if mesh is not None:
                raise ValueError(
                    f"{cfg.name}: params_dtype=auto cannot be served on a "
                    f"mesh (the int8 half is invisible to the TP rules and "
                    f"the W8A16 Pallas kernel is single-device); drop the "
                    f"mesh for this model or use params_dtype=bfloat16")
        if str(params_dtype) == "int8":
            # The W8A16 lane is a param-tree REWRITE (kernel -> kernel_q +
            # scale), not a cast; servables that support it (models/gpt2.py)
            # do it themselves at build time.  astype(int8) on float weights
            # here would destroy them.
            params_dtype = None

            def _has_q(node):
                return isinstance(node, dict) and (
                    "kernel_q" in node or any(_has_q(v) for v in node.values()))

            if not _has_q(servable.params):
                # The builder ignored the flag (model family without an int8
                # lane): refuse rather than silently serve fp32-at-rest —
                # strictly worse than the bfloat16 the operator passed over.
                raise ValueError(
                    f"{cfg.name}: params_dtype=int8 requested but this "
                    f"model family has no int8 lane (no quantized kernels "
                    f"in the param tree); use params_dtype=bfloat16")
            if mesh is not None:
                # The family TP rules match ".../kernel$" — quantized
                # kernel_q/scale nodes would silently replicate (no TP), and
                # the SPMD partitioner can't split the Pallas matmul anyway.
                # Fail at boot, not with a wrong-but-running config.
                raise ValueError(
                    f"{cfg.name}: params_dtype=int8 cannot be served on a "
                    f"mesh (quantized kernels are invisible to the TP rules "
                    f"and the W8A16 Pallas kernel is single-device); drop "
                    f"the mesh for this model or use params_dtype=bfloat16")
        if params_dtype:
            # At-rest weight dtype (e.g. "bfloat16"): halves HBM capacity vs
            # fp32 AND removes the per-call cast XLA otherwise hoists into a
            # materialized copy — measured ~10% on gpt2 generation (weight-
            # bandwidth-bound). Only ≥2-D float leaves convert: LayerNorm/BN
            # scales and biases stay fp32 for the fp32 norm paths.
            from ..models.vision_common import cast_params_at_rest, resolve_dtype

            servable.params = cast_params_at_rest(
                servable.params, resolve_dtype(params_dtype))
        if mesh is not None:
            from ..parallel.mesh import shard_params

            if isinstance(servable.params, dict) \
                    and "__adapters__" in servable.params:
                # The family TP rules can't see the stacked low-rank
                # factors (they'd silently replicate while the base kernels
                # shard — wrong math at the delta add).  Fail at boot.
                raise ValueError(
                    f"{cfg.name}: adapter_slots cannot be served on a mesh; "
                    f"drop the mesh for this model or its adapters")
            self._data_par = mesh.shape.get("data", 1)
            servable.params = shard_params(
                mesh, servable.params, servable.meta.get("tp_rules", ()))
        if servable.bucket_axes == ("batch",):
            self.buckets = sorted((int(b),) for b in cfg.batch_buckets)
        elif servable.bucket_axes == ("batch", "seq"):
            self.buckets = sorted((int(b), int(s)) for b, s in
                                  itertools.product(cfg.batch_buckets, cfg.seq_buckets))
        else:
            raise ValueError(f"unsupported bucket axes {servable.bucket_axes}")
        self.max_batch = max(b[0] for b in self.buckets)
        # Serving goes through the regular jit callable, NOT AOT
        # lower().compile() executables: the jit path keeps XLA's C++ fast
        # dispatch (~0.2 ms/call with device inputs vs ~5 ms through an AOT
        # executable's Python argument processing, measured on the v5e).
        # Warmup triggers one traced compile per bucket shape; the persistent
        # compile cache still applies.
        self._jit = jax.jit(servable.apply_fn)
        self._warmed: set[tuple[int, ...]] = set()
        self._seen: set = set()  # what this lane has compiled (the ledger's)
        # Multi-process lockstep lead hook (parallel/lockstep.py), set by
        # build_engine on process 0 of a multi-host world: run_batch
        # broadcasts each collated batch to the follower loops before
        # dispatching, so every process executes the same program.
        self.lockstep = None

    # -- bucket selection ---------------------------------------------------
    def bucket_for(self, batch: int, seq: int | None = None) -> tuple[int, ...]:
        for b in self.buckets:
            if b[0] >= batch and (seq is None or len(b) == 1 or b[1] >= seq):
                return b
        raise ValueError(
            f"{self.servable.name}: no bucket fits batch={batch} seq={seq} "
            f"(buckets={self.buckets})")

    # -- placement ----------------------------------------------------------
    def _place(self, batch: dict[str, Any]):
        """Transfer a collated batch to device(s).

        DP-shards rows over ``data`` when the bucket divides evenly;
        replicates otherwise (TP-only lane for small/odd buckets).
        """
        if self.mesh is None:
            return jax.device_put(batch)
        from ..parallel.mesh import batch_sharding, replicated

        rows = min((np.asarray(v).shape[0] for v in batch.values()), default=0)
        if self._data_par > 1 and rows and rows % self._data_par == 0:
            shardings = {k: batch_sharding(self.mesh, np.asarray(v).ndim)
                         for k, v in batch.items()}
        else:
            shardings = {k: replicated(self.mesh) for k in batch}
        return jax.device_put(batch, shardings)

    def _fetch(self, out):
        """Device→host for a result tree.

        On a multi-host mesh the data-sharded output rows live on OTHER
        processes (np.asarray would raise on non-addressable shards);
        ``process_allgather`` runs a host-level collective so every process
        gets the full batch — which lockstep serving needs anyway.
        Replicated/scalar outputs pass through un-tiled (verified: a P()
        output keeps its shape).  Single-process: plain blocking fetch.
        """
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            out = multihost_utils.process_allgather(out, tiled=True)
        return jax.tree.map(np.asarray, out)

    # -- compilation --------------------------------------------------------
    def _first_dispatch(self, bucket: tuple[int, ...], batch, wait):
        """A bucket's first run: where its program compiles or is restored
        from the cache.  Booked as a first use in the engine's ledger
        (``engine/cache.py``), whose listeners time the stages from inside;
        the bucket is warm from here on, so /healthz ``buckets_compiled`` and
        /v1/models tell the truth whether boot warmed it or a request did."""
        with self.clock.open(self.servable.name, "predict",
                             {"bucket": list(bucket)},
                             seen=self._seen) as use:
            out = self._jit(self.servable.params, batch)
            use.launched()
            out = wait(out)
        self._warmed.add(bucket)
        return out

    def _warm_bucket(self, bucket: tuple[int, ...]):
        spec = self.servable.input_spec(bucket)
        # Same placement as serving: warmup must compile the SPMD program the
        # request path will hit, or the first real request recompiles.
        self._first_dispatch(bucket, self._place(
            {k: np.zeros(s.shape, s.dtype) for k, s in spec.items()}),
            jax.block_until_ready)

    def warmup(self):
        """Compile every bucket at boot (hits the persistent cache on re-boot)."""
        for b in self.buckets:
            if b not in self._warmed:
                self._warm_bucket(b)
        self._warm_chunked()

    def _warm_chunked(self):
        """Compile the chunked-serving programs (meta['chunked']) at boot.

        The chunked path is THE job-serving path for models that declare it
        (runner.run_chunked), so a prod boot must warm prepare/chunk/finalize
        too or the first job pays three compiles.  One pass through the
        smallest bucket covers the steady-state shapes; a ragged final chunk
        (num_steps % chunk_steps != 0) compiles its second row shape as well.
        """
        ch = self.servable.meta.get("chunked")
        if ch is None or getattr(self, "_chunk_warmed", False):
            return
        bucket = self.buckets[0]
        spec = self.servable.input_spec(bucket)
        dummy = [{k: np.zeros(s.shape[1:], s.dtype) for k, s in spec.items()}
                 for _ in range(bucket[0])]
        with self.clock.open(self.servable.name, "predict",
                             {"bucket": list(bucket),
                              "chunks": ch["num_chunks"]}, seen=self._seen):
            self.chunk_finalize(self._warm_chunk_steps(dummy), dummy)
        self._chunk_warmed = True

    def _warm_chunk_steps(self, dummy):
        ch = self.servable.meta["chunked"]
        _, state = self.chunk_prepare(dummy)
        seen_shapes = set()
        for rows in ch["chunk_rows"]:
            shape = tuple(sorted((k, np.asarray(v).shape)
                                 for k, v in rows.items()))
            if shape in seen_shapes:
                continue  # same program; don't re-run every chunk at boot
            seen_shapes.add(shape)
            state = self.chunk_step(state, rows)
        return state

    # -- chunked execution (QoS preemption points; runner.run_chunked) -------
    def chunk_prepare(self, samples: Sequence[dict]):
        """Collate + place one batch and run the chunked 'prepare' program.

        Returns (bucket, device state) — the state (latents + conditioning
        for sd15) stays on device between chunk dispatches.
        """
        ch = self.servable.meta["chunked"]
        bucket = self.bucket_for(len(samples))
        spec = self.servable.input_spec(bucket)
        collate = self.servable.meta.get("collate") or default_collate
        with jax.profiler.TraceAnnotation("collate"):
            batch = collate(samples, bucket, spec)
        with jax.profiler.TraceAnnotation("h2d"):
            batch = self._place(batch)
        state = ch["prepare"](self.servable.params, batch)
        return bucket, jax.block_until_ready(state)

    def chunk_step(self, state, rows):
        """One chunk of the model's loop; blocks so lane occupancy is real."""
        ch = self.servable.meta["chunked"]
        return jax.block_until_ready(
            ch["chunk"](self.servable.params, state, rows))

    def chunk_finalize(self, state, samples: Sequence[dict]):
        """Decode + fetch + per-sample postprocess (mirror of run_batch's tail)."""
        ch = self.servable.meta["chunked"]
        out = self._fetch(ch["finalize"](self.servable.params, state))
        with jax.profiler.TraceAnnotation("postprocess"):
            return [self.servable.postprocess(out, i)
                    for i in range(len(samples))]

    @property
    def warmed_buckets(self) -> set[tuple[int, ...]]:
        return set(self._warmed)

    # -- residency tiering (serving/lifecycle.py) ----------------------------
    def param_nbytes(self) -> int:
        """Total parameter bytes — the live-HBM accounting unit the
        lifecycle manager budgets against (DeviceRunner.track_model)."""
        total = 0
        for leaf in jax.tree.leaves(self.servable.params):
            n = getattr(leaf, "nbytes", None)
            if n is None:
                try:
                    n = np.asarray(leaf).nbytes
                except Exception:
                    n = 0
            total += int(n)
        return total

    def host_offload(self):
        """Demote to the host-weights tier: fetch params to host RAM and
        release the device copies.  The jit executables stay cached in
        process keyed by the (unchanged) avals, so :meth:`device_restore`
        re-activates with a device_put and zero recompiles — the middle rung
        of the lifecycle cost ladder (device < host < compiled-cache-only).
        Single-device only; the lifecycle manager never tiers mesh/lockstep
        serving.
        """
        self.servable.params = jax.device_get(self.servable.params)

    def device_restore(self):
        """Re-promote host-resident weights to the device (lifecycle WARMING
        from the host tier)."""
        self.servable.params = jax.device_put(self.servable.params)

    def disk_offload(self, save_fn):
        """Demote to the disk tier, one rung below :meth:`host_offload`:
        hand the host-resident param tree to ``save_fn`` (the streaming
        checkpoint store, serving/ckptstore.py) and release BOTH copies.
        The model keeps this shell — jit executables stay cached keyed by
        the (unchanged) avals — so :meth:`disk_restore` is a streamed read
        + device_put with zero recompiles: the full ladder is
        device < host < disk < compiled-cache-only < cold build.
        """
        params = self.servable.params
        if params is None:
            raise RuntimeError(f"{self.cfg.name}: no params to disk_offload")
        save_fn(jax.device_get(params))
        self.servable.params = None

    def disk_restore(self, load_fn):
        """Re-promote disk-tier weights (lifecycle WARMING from disk):
        ``load_fn`` streams the tree back — its ``place_fn`` does the
        per-tensor device_put inside the overlap pipeline, so the params
        land already device-resident."""
        params = load_fn()
        if params is None:
            raise RuntimeError(f"{self.cfg.name}: disk restore returned "
                               "no params")
        self.servable.params = jax.device_put(params)

    # -- execution ----------------------------------------------------------
    def run_batch(self, samples: Sequence[dict[str, np.ndarray]],
                  seq: int | None = None) -> tuple[list[Any], tuple[int, ...]]:
        """Pad samples into a bucket, run on device, postprocess each sample.

        Returns (per-sample results, bucket used).
        """
        if seq is None and self.servable.bucket_axes == ("batch", "seq"):
            seq = max(self.servable.meta["seq_len_of"](s) for s in samples)
        bucket = self.bucket_for(len(samples), seq)
        spec = self.servable.input_spec(bucket)
        collate = self.servable.meta.get("collate") or default_collate
        # TraceAnnotations decompose the serving step into host phases for
        # /admin/profile captures (collate → h2d → device+d2h → postprocess).
        with jax.profiler.TraceAnnotation("collate"):
            batch = collate(samples, bucket, spec)
        if self.lockstep is not None:
            # Host 0 of a multi-host world: mirror this dispatch to the
            # follower loops (they place + run the identical program).
            self.lockstep.lead(self, bucket, batch)
        # Explicit transfer first: the jit call then takes the ~0.2 ms
        # device-input fast path instead of per-arg host staging.  On a mesh,
        # placement shards the batch rows over ``data`` (computation follows
        # data under jit, so this single device_put is the whole DP story).
        with jax.profiler.TraceAnnotation("h2d"):
            batch = self._place(batch)
        with jax.profiler.TraceAnnotation("device"):
            if bucket in self._warmed:
                out = self._fetch(  # blocks until ready
                    self._jit(self.servable.params, batch))
            else:  # lazy compile (warmup_at_boot: false, the dev default)
                out = self._first_dispatch(bucket, batch, self._fetch)
        with jax.profiler.TraceAnnotation("postprocess"):
            return ([self.servable.postprocess(out, i) for i in range(len(samples))],
                    bucket)
