"""Fault-tolerant MCMC driver: run loop, checkpoint/restart, elastic
re-sharding, capacity growth, diagnostics — over a ``Sampler`` built by
``build_sampler`` (DESIGN.md §13).

Large-scale runnability contract (DESIGN.md §10):

* every ``ckpt_every`` iterations the FULL sampler state (global params +
  gathered Z + tail buffers + RNG key) is written atomically; a restart
  resumes bitwise-identically (the state carries its own key).
* checkpoints store Z in *global* (unsharded) layout, so a restart may use a
  DIFFERENT shard count P — elastic scaling across restarts. Re-sharding is
  a pure reshape of the observation axis.
* capacity growth: if feature-slot overflow is detected (gs.overflow), the
  driver checkpoints and raises; a restart with a larger ``K_max`` pads the
  checkpointed feature axis with empty slots and resumes — growth is a
  restart event, never a silent truncation. The inverse is also a restart
  event: restoring under a SMALLER ``K_max`` compacts live features (plus
  the lowest free slots, the packed-carry block rule — DESIGN.md §14)
  into the new capacity, so shrink-after-burn-in bounds every K_max-sized
  buffer again; it refuses loudly if the live set does not fit.
* straggler policy on real meshes: synchronous collectives absorb jitter; a
  dead pod is a restart from the latest checkpoint (same path as above). The
  paper's L sub-iterations amortize sync cost; ``stale_sync`` (bounded
  staleness: that many sync-free sub-iteration passes are interleaved
  before each full iteration) exists as an opt-in knob and is non-exact.

Parallelism layout (DESIGN.md §13): the driver takes a ``SamplerSpec``
(or a legacy ``DriverConfig``, kept as a thin shim that maps the old
scattered kwargs onto a spec) and builds ONE ``Sampler`` whose
``chains`` x ``data`` axes replace the old backend enum:

* ``driver="vmap"``       — chains "none"  x data "vmap"
* ``driver="multichain"`` — chains "vmap"  x data "vmap" (R-hat/ESS/MCSE
  over the per-iteration trace in eval records)
* ``driver="shardmap"``   — chains "none"  x data "shardmap"
* ``driver="mesh"``       — chains "mesh"  x data "shardmap": C chains x
  P data shards on a 2-D ``("chains", "data")`` mesh — the composed path
  (multichain diagnostics AND real data collectives), runnable on CPU
  via ``--xla_force_host_platform_device_count``.

State crosses the driver boundary in the canonical (C?, P, N_p, K)
layout, so checkpoints are interchangeable across all layouts with the
same chain count.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

import os

from repro.checkpoint import restore, save_pytree
from repro.core.ibp import IBPHypers, SamplerSpec, build_sampler
from repro.core.ibp import convergence
from repro.core.ibp.api import DRIVERS
from repro.core.ibp.collapsed import (
    DEFAULT_REFRESH as DEFAULT_CHOL_REFRESH,
    tail_path,
)
from repro.core.ibp.hybrid import HybridGlobal, HybridShard
from repro.core.ibp.predict import (
    BankBuilder,
    SampleBank,
    heldout_joint_loglik,
    train_joint_loglik,
)

BACKENDS = tuple(DRIVERS)  # historical name for the driver grid


@dataclasses.dataclass
class DriverConfig:
    """DEPRECATED shim: the old scattered-kwarg construction surface.

    Maps 1:1 onto ``SamplerSpec`` via ``to_spec()`` (see the migration
    table in DESIGN.md §13). New code should construct a ``SamplerSpec``
    directly — the spec validates every knob combination loudly and
    expresses parallelism as composable ``chains`` x ``data`` axes
    instead of the ``driver`` enum.
    """

    P: int = 4
    K_max: int = 32
    K_tail: int = 8
    L: int = 5
    n_iters: int = 1000
    ckpt_every: int = 100
    ckpt_dir: str = "artifacts/ckpt/ibp"
    eval_every: int = 20
    seed: int = 0
    alpha: float = 3.0
    sigma_x: float = 1.0
    sigma_a: float = 1.0
    K_init: int = 4
    backend: str = "jnp"       # "jnp" | "pallas" for the uncollapsed sweep
    stale_sync: int = 0        # >0 = bounded staleness (non-exact)
    driver: str = "vmap"       # "vmap"|"multichain"|"shardmap"|"mesh"
    n_chains: int = 1          # chain count (multichain / mesh)
    sync: str = "staged"       # "staged" | "fused" master sync (collective)
    overflow_every: int = 8    # overflow-detection cadence (host sync)
    k_tail_grow: int = 0       # adaptive K_tail: max tail doublings (0=off)
    collapsed_backend: str = "fast"  # "fast" | "ref" (oracle) tail step
    chol_refresh: int = DEFAULT_CHOL_REFRESH  # "fast" refactor cadence
    k_live_buckets: str = "on"  # "on" only, as SamplerSpec
    harvest_every: int = 0     # SampleBank harvest cadence (0 = off, §15)
    harvest_burn: float = 0.5  # burn-in fraction before harvesting
    bank_path: str = ""        # bank npz ("" = <ckpt_dir>/bank.npz)

    def to_spec(self) -> SamplerSpec:
        if self.driver not in DRIVERS:
            raise ValueError(f"driver={self.driver!r} not in {BACKENDS}")
        chains, data = DRIVERS[self.driver]
        return SamplerSpec(
            P=self.P, K_max=self.K_max, K_tail=self.K_tail,
            K_init=self.K_init, alpha=self.alpha, sigma_x=self.sigma_x,
            sigma_a=self.sigma_a, L=self.L, backend=self.backend,
            collapsed_backend=self.collapsed_backend,
            chol_refresh=self.chol_refresh,
            k_live_buckets=self.k_live_buckets,
            chains=chains, data=data, n_chains=self.n_chains,
            sync=self.sync, stale_sync=self.stale_sync,
            n_iters=self.n_iters, eval_every=self.eval_every,
            ckpt_every=self.ckpt_every, ckpt_dir=self.ckpt_dir,
            overflow_every=self.overflow_every,
            k_tail_grow=self.k_tail_grow, seed=self.seed,
            harvest_every=self.harvest_every,
            harvest_burn=self.harvest_burn, bank_path=self.bank_path,
        )


def as_spec(cfg: DriverConfig | SamplerSpec) -> SamplerSpec:
    """Normalize either config surface to a validated SamplerSpec."""
    return cfg.to_spec() if isinstance(cfg, DriverConfig) else cfg


def _pad_trailing(x: jax.Array, axis: int, n: int) -> jax.Array:
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, n)
    return jnp.pad(x, pads)


class MCMCDriver:
    """Runs a built Sampler with checkpoint/restart + elastic P."""

    def __init__(self, X: np.ndarray, cfg: DriverConfig | SamplerSpec,
                 hyp: IBPHypers | None = None, X_eval: np.ndarray | None = None):
        spec = as_spec(cfg)
        self.spec = spec
        self.cfg = spec  # back-compat alias: run knobs live on the spec
        self.hyp = hyp or IBPHypers()
        self.sampler = build_sampler(spec, self.hyp, X)
        self.X_global = self.sampler.X_global
        self.N = self.sampler.N
        self.X_eval = None if X_eval is None else jnp.asarray(X_eval)
        self.history: list[dict[str, float]] = []
        # per-iteration scalar traces, one column per chain — the raw
        # material for split-R-hat / ESS in eval records
        self.trace: dict[str, list[np.ndarray]] = {"sigma_x": [], "K": []}
        self._chain_axis = self.sampler.chain_axis
        # posterior-predictive harvest (DESIGN.md §15): the builder
        # accumulates post-burn-in samples host-side at harvest cadence;
        # the built bank is persisted NEXT TO the checkpoints but is a
        # separate, self-describing artifact — serving restores it with
        # no sampler state at all (core/ibp/predict.py)
        self.bank_builder = (BankBuilder(spec.K_max)
                             if spec.harvest_every > 0 else None)
        self._bank: SampleBank | None = None
        # adaptive K_tail (DESIGN.md §12): doublings performed so far and
        # the tail_sat watermark at the last checkpoint boundary — growth
        # fires only on NEW saturation since that boundary
        self._tail_growths = 0
        self._sat_mark = 0

    # ---- state <-> checkpoint layout (global Z for elastic resharding) ----
    def _to_ckpt(self, gs: HybridGlobal, ss: HybridShard) -> dict:
        # tail buffers are NOT serialized: checkpoints are written post-sync,
        # where tails are always cleared — _from_ckpt rebuilds them empty at
        # the configured K_tail (which a restart may therefore resize)
        *lead, P, N_p, K = ss.Z.shape
        return {
            "gs": gs,
            "Z_global": ss.Z.reshape(*lead, P * N_p, K),
            "meta": {"it": gs.it},
        }

    def _shrink_features(self, gs: HybridGlobal, Zg, K_new: int):
        """Shrink restart: compact a checkpoint's feature axis into a
        SMALLER configured K_max (the capacity-growth path's inverse,
        DESIGN.md §14). The kept columns are every live feature plus the
        lowest-index free slots — the same block rule as the packed
        collapsed carry — so the posterior state is untouched and only
        dead slots are relabeled. After burn-in settles K⁺ well below a
        grown K_max, this bounds every K_max-sized buffer (and the
        packed scan's bucket ladder) again. Refuses loudly when the live
        features do not fit: shrinking never silently truncates state.
        Chain-batched checkpoints compact per chain (each chain has its
        own live set).
        """
        act = np.asarray(gs.active)
        Zg_h, A_h, pi_h = np.asarray(Zg), np.asarray(gs.A), np.asarray(gs.pi)
        lead = act.shape[:-1]  # () chainless, (C,) chainful
        act2 = act.reshape(-1, act.shape[-1])
        cols = []
        for c, a_row in enumerate(act2):
            live = np.flatnonzero(a_row > 0.5)
            if live.size > K_new:
                who = f"chain {c} of the checkpoint" if lead else \
                    "the checkpoint"
                raise ValueError(
                    f"cannot shrink to K_max={K_new}: {who} carries "
                    f"{live.size} live features; restart with "
                    f"K_max >= {live.size}"
                )
            free = np.flatnonzero(a_row <= 0.5)
            cols.append(np.sort(np.concatenate(
                [live, free[:K_new - live.size]])))
        if lead:
            C = len(cols)
            Zg_h = np.stack([Zg_h[c][..., cols[c]] for c in range(C)])
            A_h = np.stack([A_h[c][cols[c]] for c in range(C)])
            pi_h = np.stack([pi_h[c][cols[c]] for c in range(C)])
            act_h = np.stack([act2[c][cols[c]] for c in range(C)])
        else:
            Zg_h = Zg_h[..., cols[0]]
            A_h, pi_h, act_h = A_h[cols[0]], pi_h[cols[0]], act[cols[0]]
        gs = dataclasses.replace(
            gs, A=jnp.asarray(A_h), pi=jnp.asarray(pi_h),
            active=jnp.asarray(act_h),
        )
        return gs, jnp.asarray(Zg_h)

    def _from_ckpt(self, blob: dict) -> tuple[HybridGlobal, HybridShard]:
        spec = self.spec
        gs: HybridGlobal = blob["gs"]
        if gs.tail_refresh is None:  # written before the counter existed
            gs = dataclasses.replace(
                gs, tail_refresh=jnp.zeros_like(gs.tail_sat))
        Zg = blob["Z_global"]
        K_ck = Zg.shape[-1]
        if K_ck > spec.K_max:
            # shrink restart: compact live features into the smaller
            # capacity instead of refusing (growth's inverse)
            gs, Zg = self._shrink_features(gs, Zg, spec.K_max)
        if K_ck < spec.K_max:
            # capacity-growth restart: pad the feature axis with empty slots
            grow = spec.K_max - K_ck
            Zg = _pad_trailing(Zg, -1, grow)
            gs = dataclasses.replace(
                gs,
                A=_pad_trailing(gs.A, -2, grow),
                pi=_pad_trailing(gs.pi, -1, grow),
                active=_pad_trailing(gs.active, -1, grow),
                overflow=jnp.zeros_like(gs.overflow),
            )
        *lead, N, K = Zg.shape
        # elastic P is a reshape of the observation axis — the checkpoint's
        # N must survive the new config's truncation and divide by P, else
        # fail with a message instead of a deep reshape/broadcast error
        if N != self.N:
            raise ValueError(
                f"checkpoint has N={N} observations but this driver "
                f"truncated the data to N={self.N} (P={spec.P}); pick a P "
                f"that keeps N={N}"
            )
        # chain-axis compatibility is checked loudly: a single-chain
        # checkpoint must not silently restore under a chain-batched
        # template (or vice versa), and the chain count is part of the
        # state — n_chains cannot change across a restart (the layout of
        # the chain axis CAN: multichain <-> mesh restores are legal)
        if self._chain_axis:
            if not lead or lead[0] != spec.n_chains:
                raise ValueError(
                    f"checkpoint chain axis {lead or 'absent'} does not "
                    f"match configured n_chains={spec.n_chains}"
                )
        elif lead:
            raise ValueError(
                f"checkpoint carries a chain axis {lead}; restore it with "
                f"driver='multichain'/'mesh' and n_chains={lead[0]}"
            )
        P = spec.P
        # tails are cleared at every master sync, and checkpoints are only
        # written post-sync — so tail buffers are rebuilt EMPTY at the
        # CONFIGURED K_tail (a restart may widen/narrow tail exploration;
        # the checkpoint's tail width does not pin it)
        ss = HybridShard(
            Z=Zg.reshape(*lead, P, N // P, K),
            Z_tail=jnp.zeros((*lead, P, N // P, spec.K_tail), Zg.dtype),
            tail_active=jnp.zeros((*lead, P, spec.K_tail), Zg.dtype),
        )
        return gs, ss

    def _template(self):
        gs, st = self.sampler.init()
        return self._to_ckpt(gs, self.sampler.to_canonical(st))

    # ---- posterior-predictive harvest (DESIGN.md §15) ---------------------
    @property
    def bank_path(self) -> str:
        return self.spec.bank_path or os.path.join(self.spec.ckpt_dir,
                                                   "bank.npz")

    @property
    def bank(self) -> SampleBank | None:
        """The harvested ensemble as a built SampleBank (None before the
        first harvest). Rebuilt lazily when new samples arrived."""
        b = self.bank_builder
        if b is None or len(b) == 0:
            return self._bank
        if self._bank is None or self._bank.S != len(b):
            self._bank = b.build()
        return self._bank

    def save_bank(self) -> str | None:
        """Build + persist the bank (npz, restorable with no sampler
        state). Returns the path, or None if nothing was harvested."""
        bank = self.bank
        if bank is None:
            return None
        return bank.save(self.bank_path)

    # ---- adaptive K_tail (DESIGN.md §12) ----------------------------------
    def _maybe_grow_tail(self, gs: HybridGlobal, ss: HybridShard):
        """Double K_tail when NEW tail saturation accrued since the last
        checkpoint boundary (capacity-vetoed accepted births on p' —
        gs.tail_sat), bounded by ``k_tail_grow`` doublings and the K_max
        ceiling. Runs exactly at a post-sync checkpoint boundary: tails
        are always cleared there, so the sampler is rebuilt in-process
        with EMPTY tail buffers at the new width and the posterior state
        is untouched — growth is a pure widening of future exploration,
        not a restart. The counter resets so the next decision sees only
        post-growth saturation. Returns (gs, ss, grew)."""
        spec = self.spec
        sat = int(jnp.max(gs.tail_sat))
        grew = False
        if (self._tail_growths < spec.k_tail_grow
                and spec.K_tail < spec.K_max and sat > self._sat_mark):
            new_tail = min(2 * spec.K_tail, spec.K_max)
            spec = spec.replace(K_tail=new_tail)
            self.spec = self.cfg = spec
            self.sampler = build_sampler(spec, self.hyp, self.X_global)
            *lead, P, N_p, _ = ss.Z.shape
            ss = HybridShard(
                Z=ss.Z,
                Z_tail=jnp.zeros((*lead, P, N_p, new_tail), ss.Z.dtype),
                tail_active=jnp.zeros((*lead, P, new_tail), ss.Z.dtype),
            )
            gs = dataclasses.replace(gs,
                                     tail_sat=jnp.zeros_like(gs.tail_sat))
            self._tail_growths += 1
            grew = True
        self._sat_mark = int(jnp.max(gs.tail_sat))
        return gs, ss, grew

    # ---- main loop --------------------------------------------------------
    def run(self, n_iters: int | None = None,
            on_eval: Callable[[dict], None] | None = None,
            crash_at: int | None = None):
        """Main loop. ``crash_at`` raises mid-run (for restart tests).

        Each cadence phase runs inside a ``jax.profiler.TraceAnnotation``
        (``ibp:harvest``, ``ibp:poll``, ``ibp:canonical``, ``ibp:eval``,
        ``ibp:ckpt``), on the profiler's clock, so a trace taken under
        ``jax.profiler.trace`` puts each device-idle gap down to a phase
        (DESIGN.md §16). With no profiler active a span costs about a
        microsecond.
        """
        spec = self.spec
        sampler = self.sampler
        n_iters = n_iters or spec.n_iters
        tpl = self._template()
        # checkpoints written before gs carried tail_refresh restore it at 0
        legacy = dict(tpl, gs=dataclasses.replace(tpl["gs"],
                                                  tail_refresh=None))
        restored = restore(spec.ckpt_dir, tpl, older=(legacy,))
        if restored is not None:
            blob, start = restored[0], int(restored[1])
            gs, ss = self._from_ckpt(blob)
            gs = sampler.place_global(gs)
            st = sampler.from_canonical(ss)  # native, device-resident
            # a restart continues the harvest from the persisted bank
            # instead of overwriting it with a shorter ensemble...
            if (self.bank_builder is not None
                    and len(self.bank_builder) == 0
                    and os.path.exists(self.bank_path)):
                self.bank_builder.extend_from(SampleBank.load(self.bank_path))
            # ...and reconciles it with the REWIND: iterations past the
            # restored step re-run and re-harvest, so samples beyond it
            # are dropped first — whether they came from the persisted
            # bank (bank saved after the restored checkpoint) or from
            # this same driver object's interrupted run() — keeping
            # every draw exactly once in the ensemble
            if self.bank_builder is not None:
                self.bank_builder.prune_after(start)
                self._bank = None
        else:
            start = 0
            gs, st = sampler.init(jax.random.key(spec.seed))
            # fresh start = iteration 0: an interrupted same-object run()
            # that never checkpointed must not leak its harvests into
            # this rerun (the iterations re-run and re-harvest)
            if self.bank_builder is not None:
                self.bank_builder.prune_after(0)
                self._bank = None

        t0 = time.time()
        span = jax.profiler.TraceAnnotation
        for it in range(start, n_iters):
            if crash_at is not None and it == crash_at:
                raise RuntimeError(f"injected crash at iteration {it}")
            for _ in range(spec.stale_sync):
                gs, st = sampler.stale(gs, st)
            gs, st = sampler.step(gs, st)
            self._record_trace(gs)
            last = it == n_iters - 1
            # harvest the post-sync posterior draw into the sample bank
            # (host transfer of the K_max-sized params only — never Z)
            if (self.bank_builder is not None
                    and (it + 1) > int(spec.harvest_burn * n_iters)
                    and (it + 1) % spec.harvest_every == 0):
                with span("ibp:harvest"):
                    self.bank_builder.add_state(gs, it=it + 1)
            need_eval = (it + 1) % spec.eval_every == 0 or last
            need_ckpt = (it + 1) % spec.ckpt_every == 0 or last
            # pulling gs.overflow blocks the host on the iteration's whole
            # computation, so check at a bounded cadence, not every step —
            # detection delay is <= overflow_every iterations (DESIGN.md §10)
            overflowed = False
            if (need_eval or need_ckpt
                    or (it + 1) % spec.overflow_every == 0):
                with span("ibp:poll"):
                    overflowed = int(jnp.max(gs.overflow)) > 0
            if need_eval or need_ckpt or overflowed:
                # canonical layout is materialized at cadence only — the
                # hot loop never leaves the layout's native state
                with span("ibp:canonical"):
                    ss = sampler.to_canonical(st)
            if need_eval:
                with span("ibp:eval"):
                    rec = self.evaluate(gs, ss, it + 1, time.time() - t0)
                self.history.append(rec)
                if on_eval:
                    on_eval(rec)
            if need_ckpt:
                # the bank rides the checkpoint cadence for durability
                # (own self-describing file), and is written FIRST: a
                # crash between the two writes then rewinds to an older
                # checkpoint whose re-run re-harvests — prune_after on
                # restore reconciles — whereas checkpoint-first would
                # resume PAST unsaved harvests and lose them forever
                with span("ibp:ckpt"):
                    if (self.bank_builder is not None
                            and len(self.bank_builder)):
                        self.save_bank()
                    save_pytree(spec.ckpt_dir, self._to_ckpt(gs, ss), it + 1)
                    # adaptive K_tail rides the checkpoint boundary (the
                    # one place tails are provably empty): saturation
                    # since the last boundary doubles the tail width
                    # in-process — the just-written checkpoint stays
                    # valid (tails are not serialized; a restart re-grows
                    # if saturation returns)
                    if spec.k_tail_grow > 0 and not last and not overflowed:
                        gs, ss, grew = self._maybe_grow_tail(gs, ss)
                        if grew:
                            spec = self.spec
                            sampler = self.sampler
                            st = sampler.from_canonical(ss)
            if overflowed:
                # capacity growth: checkpoint + restart with larger K_max.
                # the bank is saved too (bank-first, as above) — the
                # restart resumes AFTER this iteration, so harvests since
                # the last cadence save would otherwise be dropped
                if not need_ckpt:
                    with span("ibp:ckpt"):
                        if (self.bank_builder is not None
                                and len(self.bank_builder)):
                            self.save_bank()
                        save_pytree(spec.ckpt_dir, self._to_ckpt(gs, ss),
                                    it + 1)
                raise RuntimeError(
                    f"K_max={spec.K_max} overflow at it={it}; restart with "
                    f"2x K_max"
                )
        return gs, sampler.to_canonical(st)

    # ---- diagnostics ------------------------------------------------------
    def _record_trace(self, gs: HybridGlobal) -> None:
        # keep DEVICE arrays: np.asarray here would block on every
        # iteration's whole computation and kill async dispatch — the
        # host sync is deferred to diagnostics() (eval cadence)
        self.trace["sigma_x"].append(jnp.atleast_1d(gs.sigma_x))
        self.trace["K"].append(jnp.atleast_1d(jnp.sum(gs.active, axis=-1)))

    def diagnostics(self, burn_frac: float = 0.5) -> dict[str, float]:
        """split-R-hat / ESS / MCSE of the monitored scalars over the
        post-burn tail of the per-iteration trace (DESIGN.md §11).
        R-hat is NaN until the trace has enough post-burn draws."""
        out: dict[str, float] = {}
        for name, rows in self.trace.items():
            # convert each device row to host numpy ONCE, in place —
            # releases the device buffer and keeps repeat evals linear
            for i, r in enumerate(rows):
                if not isinstance(r, np.ndarray):
                    rows[i] = np.asarray(r, np.float64)
            if len(rows) < 8:
                continue
            arr = np.stack(rows, axis=1)               # (C, T)
            tail = arr[:, int(burn_frac * arr.shape[1]):]
            s = convergence.summarize(tail, name)
            for k in ("rhat", "ess", "mcse"):
                out[f"{name}_{k}"] = s[f"{name}_{k}"]
        return out

    def evaluate(self, gs: HybridGlobal, ss: HybridShard, it: int,
                 elapsed: float) -> dict[str, Any]:
        X = self.sampler.X
        # the held-out scorer runs the gaussian_sse kernel outside any
        # shard_map, and a compiled Pallas kernel cannot be partitioned
        # over a mesh: it scores one device's copy of the parameters
        gs_ev = (gs if self.sampler.mesh is None
                 else jax.device_put(gs, jax.devices()[0]))
        # which path the tail's row scan took: the fused kernel or the scan
        path = tail_path(self.spec.collapsed_backend)
        if self._chain_axis:
            C = ss.Z.shape[0]
            Z = ss.Z.reshape(C, self.N, -1)
            lls = jax.vmap(
                train_joint_loglik, in_axes=(None, 0, 0, 0, 0, 0)
            )(X, Z, gs.A, gs.pi, gs.active, gs.sigma_x)
            Ks = np.asarray(jnp.sum(gs.active, axis=-1))
            rec: dict[str, Any] = {
                "it": it,
                "t": elapsed,
                "K": float(Ks.mean()),
                "K_chains": [int(k) for k in Ks],
                "alpha": float(jnp.mean(gs.alpha)),
                "sigma_x": float(jnp.mean(gs.sigma_x)),
                "sigma_x_chains": [float(s) for s in np.asarray(gs.sigma_x)],
                "joint_ll_train": float(jnp.mean(lls)),
                "joint_ll_train_chains": [float(l) for l in np.asarray(lls)],
                "K_tail": int(self.spec.K_tail),
                "tail_sat": int(jnp.max(gs.tail_sat)),
                "tail_sat_chains": [int(s)
                                    for s in np.asarray(gs.tail_sat)],
                "tail_refresh": int(jnp.max(gs.tail_refresh)),
                "tail_refresh_chains": [
                    int(r) for r in np.asarray(gs.tail_refresh)],
                "tail_path": path,
            }
            if self.X_eval is not None:
                ev = jax.vmap(
                    lambda A, pi, act, sx, k: heldout_joint_loglik(
                        self.X_eval, A, pi, act, sx,
                        jax.random.fold_in(k, 999),
                    )
                )(gs_ev.A, gs_ev.pi, gs_ev.active, gs_ev.sigma_x, gs_ev.key)
                rec["joint_ll_eval"] = float(jnp.mean(ev))
        else:
            Z = ss.Z.reshape(self.N, -1)
            rec = {
                "it": it,
                "t": elapsed,
                "K": int(jnp.sum(gs.active)),
                "alpha": float(gs.alpha),
                "sigma_x": float(gs.sigma_x),
                "joint_ll_train": float(train_joint_loglik(
                    X, Z, gs.A, gs.pi, gs.active, gs.sigma_x
                )),
                "K_tail": int(self.spec.K_tail),
                "tail_sat": int(gs.tail_sat),
                "tail_refresh": int(gs.tail_refresh),
                "tail_path": path,
            }
            if self.X_eval is not None:
                rec["joint_ll_eval"] = float(heldout_joint_loglik(
                    self.X_eval, gs_ev.A, gs_ev.pi, gs_ev.active,
                    gs_ev.sigma_x, jax.random.fold_in(gs_ev.key, 999),
                ))
        rec.update(self.diagnostics())
        return rec
