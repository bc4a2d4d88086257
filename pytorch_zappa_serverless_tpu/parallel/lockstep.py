"""Multi-host follower driver: ONE HTTP endpoint over a cross-host mesh.

Multi-controller JAX is lockstep SPMD — every process must dispatch the same
programs in the same order (README "Multi-host" topology 2).  Round 2 shipped
the library surface (identical ``run_batch`` calls on every host, driven
externally); this module closes the documented gap: **host 0 terminates
HTTP and leads, follower hosts run a loop that mirrors its dispatches**, so
a load balancer needs exactly one backend and followers need no request
plumbing at all.

Protocol (all control flow rides ``multihost_utils.broadcast_one_to_all``,
itself a lockstep collective on tiny arrays — no side channel, no sockets
beyond what jax.distributed already has):

1. header ``int32[4] = [op, model_idx, batch, seq]`` — op 1=run, 2=shutdown;
   model_idx indexes ``sorted(engine.models)`` (identical config on every
   host); seq is -1 for batch-only buckets.
2. op=run: the collated batch pytree follows (followers contribute
   zeros shaped from ``input_spec(bucket)`` — broadcast output is host 0's
   values everywhere), then every process places + runs the SAME jitted
   program and joins the result allgather (``CompiledModel._fetch``).

The lead side hooks ``CompiledModel.run_batch`` between collate and
placement (``lockstep`` attribute, set by ``engine/loader.build_engine`` on
multi-process worlds), so every serving lane — batcher, jobs, warmup-after-
boot lazy compiles — is mirrored without knowing the driver exists.

Liveness: followers block in the header collective until host 0 leads
again; on DCN deployments set a collective timeout generously above the
longest idle gap, or run a cron ping against host 0 (each request leads a
broadcast, doubling as the heartbeat).  ``/healthz``'s device probe is
process-local (no collectives) and stays safe on every host.
"""

from __future__ import annotations

import numpy as np

from ..utils.logging import get_logger, log_event

log = get_logger("parallel.lockstep")

OP_RUN = 1
OP_SHUTDOWN = 2
OP_GEN_ADMIT = 3    # [op, model_idx, admit_bucket, slot] + admit_spec payload
OP_GEN_SEGMENT = 4  # [op, model_idx, 0, 0] + slot state
#                     (tok, pos, step, fin, temp, seed, topk, topp)
OP_HEARTBEAT = 5    # [op, 0, 0, 0] — liveness tick, no payload


class LockstepContractError(ValueError):
    """Collate output violated the broadcast spec — raised on the leader
    BEFORE any broadcast, so the world is still in lockstep and only the
    offending request needs to fail (callers must NOT escalate this to the
    post-broadcast world-fatal path)."""


def _check_payload(name: str, kind: str, payload: dict, spec: dict,
                   bucket) -> None:
    """Keys/shapes/dtypes of ``payload`` must match ``spec`` exactly:
    followers rebuild the pytree from the spec, so any drift desyncs the
    broadcast deep in a collective instead of failing loudly here."""
    if set(payload) != set(spec):
        raise LockstepContractError(
            f"{name}: {kind} keys {sorted(payload)} != spec keys "
            f"{sorted(spec)} for bucket {bucket}")
    for key, s in spec.items():
        arr = np.asarray(payload[key])
        if tuple(arr.shape) != tuple(s.shape) or arr.dtype != s.dtype:
            raise LockstepContractError(
                f"{name}.{key}: {kind} produced {arr.dtype}{list(arr.shape)} "
                f"but the spec for bucket {bucket} declares "
                f"{s.dtype}{list(s.shape)}")


class LockstepDriver:
    """Broadcast-mirrored dispatch for one multi-process engine."""

    def __init__(self, engine):
        self.engine = engine
        self.model_names = sorted(engine.models)
        self._down = False
        # False until Engine.enable_lockstep_lead(): the library lockstep
        # pattern (every host drives run_batch itself) must not broadcast.
        self.lead_enabled = False

    @staticmethod
    def _broadcast(tree):
        from jax.experimental import multihost_utils

        return multihost_utils.broadcast_one_to_all(tree)

    # -- host 0 -------------------------------------------------------------
    def lead(self, cm, bucket: tuple[int, ...], batch: dict) -> None:
        """Announce + ship one collated batch (dispatch thread, host 0)."""
        if self._down:
            raise RuntimeError("lockstep driver is shut down")
        # Contract check BEFORE broadcasting (ADVICE r3): failing here fails
        # only this request, loudly, on the leader — pre-broadcast, so the
        # world stays in lockstep.
        _check_payload(cm.servable.name, "collate", batch,
                       cm.servable.input_spec(bucket), bucket)
        mi = self.model_names.index(cm.servable.name)
        seq = bucket[1] if len(bucket) > 1 else -1
        self._broadcast(np.asarray([OP_RUN, mi, bucket[0], seq], np.int32))
        self._broadcast(batch)

    def lead_gen_admit(self, model: str, slot: int, bucket: int,
                       payload: dict) -> None:
        """Mirror one streaming admission (prefill + insert); dispatch thread.

        ``payload`` is whatever the servable's ``collate_admit`` produced —
        followers reconstruct the matching zero pytree from the servable's
        ``admit_spec(bucket)``, so the wire format is model-shaped (token
        ids for gpt2, log-mel audio for whisper) without protocol changes.
        """
        if self._down:
            raise RuntimeError("lockstep driver is shut down")
        # Same pre-broadcast contract check as lead() (ADVICE r4): a
        # collate_admit/admit_spec drift fails THIS request on the leader
        # instead of desyncing the follower broadcast — the scheduler maps
        # LockstepContractError to its per-request (non-fatal) path.
        cm = self.engine.models[model]
        _check_payload(model, "collate_admit", payload,
                       cm.servable.meta["continuous"]["admit_spec"](bucket),
                       bucket)
        mi = self.model_names.index(model)
        self._broadcast(np.asarray([OP_GEN_ADMIT, mi, bucket, slot], np.int32))
        self._broadcast(payload)

    def lead_gen_segment(self, model: str, state: dict) -> None:
        """Mirror one decode segment over the slot pool; dispatch thread."""
        if self._down:
            raise RuntimeError("lockstep driver is shut down")
        mi = self.model_names.index(model)
        self._broadcast(np.asarray([OP_GEN_SEGMENT, mi, 0, 0], np.int32))
        self._broadcast(state)

    def lead_heartbeat(self) -> None:
        """No-op liveness tick (dispatch thread, host 0).

        Closes the r3 idle-follower caveat: between requests followers sit
        inside the header collective with no bound on how long; a periodic
        heartbeat keeps that wait under ``heartbeat_interval_s``, so DCN
        collective timeouts can be set tight and a dead leader is noticed
        by its missing tick instead of by an unbounded hang.
        """
        if self._down:
            raise RuntimeError("lockstep driver is shut down")
        self._broadcast(np.asarray([OP_HEARTBEAT, 0, 0, 0], np.int32))

    def lead_shutdown(self) -> None:
        """Release follower loops (host 0, once, at engine shutdown)."""
        if not self._down:
            self._down = True
            self._broadcast(np.asarray([OP_SHUTDOWN, 0, 0, 0], np.int32))

    # -- followers ----------------------------------------------------------
    def _gen_state(self, name: str):
        """Per-model mirrored generation kernels + cache pool (lazy)."""
        state = self._gen.get(name)
        if state is None:
            from ..serving.generation import build_gen_kernels, slot_program
            from ..serving.tracing import RoundTimeline

            cm = self.engine.models[name]
            kernels = build_gen_kernels(cm, self.engine.mesh)
            state = self._gen[name] = {
                "kernels": kernels,
                "cache": kernels["alloc_cache"](),
                # The leader's launch phases at the same points, so that a
                # mirrored program's first use is booked as the leader's is.
                "timeline": RoundTimeline(name, clock=cm.clock,
                                          program_of=slot_program),
            }
        return state

    def _follow_gen_admit(self, name: str, slot: int, bucket: int,
                          payload: dict):
        state = self._gen_state(name)
        k, tl = state["kernels"], state["timeline"]
        cm = self.engine.models[name]
        with tl.phase("prefill.launch", programs=1, batch=1, bucket=bucket,
                      slots=str(slot)):
            first, *cache = k["prefill"](cm.servable.params, state["cache"],
                                         np.asarray([slot], np.int32),
                                         payload)
            state["cache"] = tuple(cache)
        with tl.phase("prefill.fetch"):
            np.asarray(first)  # completion fence, mirroring the leader's

    def _follow_gen_segment(self, name: str, st: dict):
        state = self._gen_state(name)
        k, tl = state["kernels"], state["timeline"]
        cm = self.engine.models[name]
        with tl.phase("segment.launch", programs=1):
            packed, *cache = k["segment"](
                cm.servable.params, state["cache"], st["tok"], st["pos"],
                st["step"], st["fin"], st["temp"], st["seed"], st["topk"],
                st["topp"])
            state["cache"] = tuple(cache)
        with tl.phase("segment.fetch"):
            np.asarray(packed)  # completion fence, mirroring the leader's

    def follow(self) -> None:
        """Mirror host 0's dispatches until it shuts down (blocking)."""
        import jax

        self._gen: dict[str, dict] = {}
        log_event(log, "follower ready", process=jax.process_index())
        while True:
            try:
                header = np.asarray(self._broadcast(
                    np.zeros((4,), np.int32)))
            except Exception:
                # A dead leader surfaces as a failed/timed-out collective
                # (e.g. host 0 SIGKILLed before it could lead the shutdown).
                # Exit the loop cleanly so process supervisors can restart
                # the whole world, instead of crash-looping inside jax.
                log.exception("lockstep header collective failed; assuming "
                              "leader loss")
                return
            op, mi, b, s = (int(x) for x in header)
            if op == OP_HEARTBEAT:
                continue
            if op == OP_SHUTDOWN:
                log_event(log, "follower released")
                return
            try:
                name = self.model_names[mi]
                cm = self.engine.models[name]
                if op == OP_GEN_ADMIT:
                    spec = cm.servable.meta["continuous"]["admit_spec"](b)
                    zeros = {key: np.zeros(v.shape, v.dtype)
                             for key, v in spec.items()}
                    payload = {k: np.asarray(v)
                               for k, v in self._broadcast(zeros).items()}
                    self._follow_gen_admit(name, s, b, payload)
                    continue
                if op == OP_GEN_SEGMENT:
                    S = cm.servable.meta["continuous"]["slots"]
                    zeros = {"tok": np.zeros((S,), np.int32),
                             "pos": np.zeros((S,), np.int32),
                             "step": np.zeros((S,), np.int32),
                             "fin": np.zeros((S,), bool),
                             "temp": np.zeros((S,), np.float32),
                             "seed": np.zeros((S,), np.int32),
                             "topk": np.zeros((S,), np.int32),
                             "topp": np.zeros((S,), np.float32)}
                    st = {k: np.asarray(v)
                          for k, v in self._broadcast(zeros).items()}
                    self._follow_gen_segment(name, st)
                    continue
                bucket = (b,) if s < 0 else (b, s)
                spec = cm.servable.input_spec(bucket)
                zeros = {k: np.zeros(v.shape, v.dtype)
                         for k, v in spec.items()}
                batch = {k: np.asarray(v)
                         for k, v in self._broadcast(zeros).items()}
                placed = cm._place(batch)
                out = cm._jit(cm.servable.params, placed)
                cm._fetch(out)  # the allgather host 0's fetch joins
            except Exception:
                # A mirrored dispatch failing on ONE side means the hosts
                # have diverged (half the collectives have no peer) — there
                # is no half-alive recovery.  Exit like the leader-loss
                # path so a process supervisor restarts the whole world;
                # the leader's next collective fails/times out rather than
                # silently wedging behind a follower that skipped a step.
                log.exception("mirrored dispatch failed on the follower; "
                              "exiting for a world restart")
                return
