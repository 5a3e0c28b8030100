"""Composable sampler API: ``SamplerSpec`` + ``build_sampler`` (DESIGN.md §13).

One frozen, validated dataclass holds EVERY sampler knob — model sizes,
kernel dispatch (``L``, ``backend``, ``collapsed_backend``,
``chol_refresh``), parallelism layout (``chains`` x
``data``, ``n_chains``, ``P``, ``sync``, ``stale_sync``) and run control
— and
``build_sampler(spec, hyp, X)`` turns it into a ``Sampler`` with a uniform
protocol:

    s = build_sampler(SamplerSpec(P=4, K_max=16, L=5), IBPHypers(), X)
    gs, st = s.init(jax.random.key(0))
    gs, st = s.step(gs, st)          # one full hybrid iteration
    gs, st = s.stale(gs, st)         # bounded-staleness pass (non-exact)
    ss = s.to_canonical(st)          # HybridShard, (C?, P, N_p, K) layout
    st = s.from_canonical(ss)        # back to the layout-native state

Parallelism is two ORTHOGONAL axes, not a driver enum:

    chains: "none" | "vmap" | "mesh"     x     data: "vmap" | "shardmap"

The historical driver names are degenerate points of that grid (see
``DRIVERS``): ``vmap`` = none x vmap, ``multichain`` = vmap x vmap,
``shardmap`` = none x shardmap, and the composed ``mesh`` = mesh x
shardmap — C chains x P data shards on a 2-D ``("chains", "data")``
mesh (runnable on CPU via ``--xla_force_host_platform_device_count``).
``chains="mesh"`` also composes with ``data="vmap"`` (real chain
parallelism, simulated data shards); only ``chains="vmap"`` x
``data="shardmap"`` is rejected — vmap of a collective program is not a
layout.

State crosses ``to_canonical`` in the canonical ``(C?, P, N_p, K)``
HybridShard layout, so checkpoints are interchangeable across every
layout with the same chain count (chainless <-> chainful restores are
rejected loudly by the driver; see runtime/driver.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from .collapsed import COLLAPSED_BACKENDS, DEFAULT_REFRESH
from .hybrid import (
    HybridShard,
    build_hybrid_fns,
    init_hybrid,
    init_multichain,
)
from .state import IBPHypers

CHAIN_MODES = ("none", "vmap", "mesh")
DATA_MODES = ("vmap", "shardmap")
SWEEP_BACKENDS = ("jnp", "pallas")
SYNC_MODES = ("staged", "fused")

# historical driver names -> (chains, data) axis modes
DRIVERS = {
    "vmap": ("none", "vmap"),
    "multichain": ("vmap", "vmap"),
    "shardmap": ("none", "shardmap"),
    "mesh": ("mesh", "shardmap"),
}


@dataclasses.dataclass(frozen=True)
class SamplerSpec:
    """All sampler knobs in one frozen, validated place.

    Invalid combinations fail at construction time with a ValueError —
    never silently downstream (a negative ``stale_sync`` used to skip the
    stale loop without a sound; a zero ``overflow_every`` used to crash
    the run loop with a bare ZeroDivisionError).
    """

    # ---- model / state sizes
    P: int = 4                 # data shards (processors p of the paper)
    K_max: int = 32            # instantiated-feature capacity
    K_tail: int = 8            # in-flight tail features on p'
    K_init: int = 4            # features seeded at init
    alpha: float = 3.0
    sigma_x: float = 1.0
    sigma_a: float = 1.0
    # ---- kernel dispatch
    L: int = 5                 # sub-iterations per master sync
    backend: str = "jnp"       # uncollapsed sweep: "jnp" | "pallas"
    collapsed_backend: str = "fast"  # tail row step: "fast" | "ref" (oracle)
    chol_refresh: int = DEFAULT_REFRESH  # fast-path refactor cadence
    k_live_buckets: str = "on"  # "on" only; the benchmark's configs pass it
    # ---- parallelism layout (axes, not an enum)
    chains: str = "none"       # "none" | "vmap" | "mesh"
    data: str = "vmap"         # "vmap" | "shardmap"
    n_chains: int = 1          # C (chain axis size; 1 when chains="none")
    sync: str = "staged"       # "staged" | "fused" master sync (shardmap)
    stale_sync: int = 0        # bounded-staleness passes/iter (non-exact)
    # ---- run control (consumed by MCMCDriver, validated here)
    n_iters: int = 1000
    eval_every: int = 20
    ckpt_every: int = 100
    ckpt_dir: str = "artifacts/ckpt/ibp"
    overflow_every: int = 8    # overflow-detection cadence (host sync)
    k_tail_grow: int = 0       # adaptive K_tail: max automatic tail
    #                            doublings at checkpoint boundaries when
    #                            the tail-saturation counter fires
    #                            (0 = fixed K_tail; ceiling is K_max)
    seed: int = 0
    # ---- posterior-predictive harvest (SampleBank, DESIGN.md §15)
    harvest_every: int = 0     # harvest a posterior sample every this many
    #                            iterations (0 = off); chain-batched runs
    #                            harvest one sample per chain
    harvest_burn: float = 0.5  # fraction of the run discarded as burn-in
    #                            before harvesting starts
    bank_path: str = ""        # SampleBank npz ("" = <ckpt_dir>/bank.npz)

    def __post_init__(self):
        def bad(msg: str):
            raise ValueError(f"SamplerSpec: {msg}")

        if self.chains not in CHAIN_MODES:
            bad(f"chains={self.chains!r} not in {CHAIN_MODES}")
        if self.data not in DATA_MODES:
            bad(f"data={self.data!r} not in {DATA_MODES}")
        if (self.chains, self.data) == ("vmap", "shardmap"):
            bad("chains='vmap' cannot compose with data='shardmap' (vmap "
                "of a collective program is not a layout; use "
                "chains='mesh')")
        if self.n_chains < 1:
            bad(f"n_chains={self.n_chains} must be >= 1")
        if self.chains == "none" and self.n_chains != 1:
            bad(f"n_chains={self.n_chains} needs a chain axis; set "
                f"chains='vmap' or 'mesh' (driver='multichain'/'mesh')")
        if self.sync not in SYNC_MODES:
            bad(f"sync={self.sync!r} not in {SYNC_MODES}")
        if self.sync == "fused" and self.data != "shardmap":
            bad(f"sync='fused' is a collective schedule; data="
                f"{self.data!r} has no collectives (use data='shardmap')")
        if self.backend not in SWEEP_BACKENDS:
            bad(f"backend={self.backend!r} not in {SWEEP_BACKENDS}")
        if self.collapsed_backend not in COLLAPSED_BACKENDS:
            bad(f"collapsed_backend={self.collapsed_backend!r} not in "
                f"{COLLAPSED_BACKENDS}")
        if self.chol_refresh < 1:
            bad(f"chol_refresh={self.chol_refresh} must be >= 1")
        if self.k_live_buckets != "on":
            bad(f"k_live_buckets={self.k_live_buckets!r} not in ('on',): "
                f"the hybrid tail always carries G = HH^T")
        if self.P < 1:
            bad(f"P={self.P} must be >= 1")
        if self.L < 1:
            bad(f"L={self.L} must be >= 1")
        if self.K_max < 1 or self.K_tail < 1:
            bad(f"K_max={self.K_max}, K_tail={self.K_tail} must be >= 1")
        if self.K_tail > self.K_max:
            bad(f"K_tail={self.K_tail} exceeds K_max={self.K_max}: tail "
                f"promotion scatters into free instantiated slots, so a "
                f"tail wider than the capacity can try to place births "
                f"with no slot to hold them (at full occupancy every "
                f"promotion would silently drop)")
        if self.k_tail_grow < 0:
            bad(f"k_tail_grow={self.k_tail_grow} must be >= 0 "
                f"(0 disables adaptive K_tail growth)")
        if not 0 <= self.K_init <= self.K_max:
            bad(f"K_init={self.K_init} must be in [0, K_max={self.K_max}]")
        if self.stale_sync < 0:
            bad(f"stale_sync={self.stale_sync} must be >= 0 (a negative "
                f"value would silently skip the stale loop)")
        if self.overflow_every < 1:
            bad(f"overflow_every={self.overflow_every} must be >= 1")
        if self.n_iters < 1 or self.eval_every < 1 or self.ckpt_every < 1:
            bad(f"n_iters={self.n_iters}, eval_every={self.eval_every}, "
                f"ckpt_every={self.ckpt_every} must all be >= 1")
        if self.harvest_every < 0:
            bad(f"harvest_every={self.harvest_every} must be >= 0 "
                f"(0 disables harvesting)")
        if not 0.0 <= self.harvest_burn < 1.0:
            bad(f"harvest_burn={self.harvest_burn} must be in [0, 1) — a "
                f"burn fraction of the run, not an iteration count")

    # ---- derived views ----------------------------------------------------
    @property
    def driver(self) -> str:
        """Historical driver name for this layout (display/CLI)."""
        if self.chains == "mesh":
            return "mesh"
        if self.chains == "vmap":
            return "multichain"
        return "shardmap" if self.data == "shardmap" else "vmap"

    @property
    def chain_axis(self) -> bool:
        """Whether state leaves carry a leading chain axis."""
        return self.chains != "none"

    @property
    def devices_needed(self) -> int:
        """Real devices this layout requires (1 for pure-vmap layouts)."""
        c = self.n_chains if self.chains == "mesh" else 1
        p = self.P if self.data == "shardmap" else 1
        return c * p

    @classmethod
    def for_driver(cls, driver: str, **kw) -> "SamplerSpec":
        """Spec for a historical driver name (the DriverConfig shim path)."""
        if driver not in DRIVERS:
            raise ValueError(f"driver={driver!r} not in {tuple(DRIVERS)}")
        chains, data = DRIVERS[driver]
        return cls(chains=chains, data=data, **kw)

    def replace(self, **kw) -> "SamplerSpec":
        return dataclasses.replace(self, **kw)


class Sampler:
    """A built sampler: uniform init/step/stale/canonicalize protocol over
    every parallelism layout. Construct via ``build_sampler``.

    The native state ``st`` stays device-resident in the layout's hot
    format across the whole run loop; ``to_canonical``/``from_canonical``
    convert to/from the canonical ``(C?, P, N_p, K)`` HybridShard layout
    (used by checkpoints and eval) at cadence only.
    """

    def __init__(self, spec: SamplerSpec, hyp: IBPHypers, X: np.ndarray):
        self.spec = spec
        self.hyp = hyp
        X = np.asarray(X, np.float32)
        N = (X.shape[0] // spec.P) * spec.P
        if N == 0:
            raise ValueError(
                f"X has {X.shape[0]} rows; need at least P={spec.P}"
            )
        self.X_global = X[:N]
        self.N, self.D = N, X.shape[1]
        self.chain_axis = spec.chain_axis
        self.mesh = self._make_mesh()
        self._flat = self.mesh is not None  # mesh-native (Z, Zt, ta) state
        self._fns = build_hybrid_fns(spec, hyp, N_global=self.N,
                                     mesh=self.mesh)
        self._Xn = self._place_data()

    # ---- construction helpers --------------------------------------------
    def _make_mesh(self):
        spec = self.spec
        if spec.data != "shardmap" and spec.chains != "mesh":
            return None
        need = spec.devices_needed
        if need > jax.device_count():
            raise ValueError(
                f"driver={spec.driver!r} needs {need} devices "
                f"({spec.n_chains if spec.chains == 'mesh' else 1} chains x "
                f"{spec.P if spec.data == 'shardmap' else 1} data shards), "
                f"have {jax.device_count()} (use "
                f"--xla_force_host_platform_device_count on CPU)"
            )
        if spec.chains == "mesh" and spec.data == "shardmap":
            shape, names = (spec.n_chains, spec.P), ("chains", "data")
        elif spec.chains == "mesh":
            shape, names = (spec.n_chains,), ("chains",)
        else:
            shape, names = (spec.P,), ("data",)
        return jax.make_mesh(shape, names,
                             axis_types=(AxisType.Auto,) * len(names))

    def _shardings(self):
        """(data-rows, chains, chains x data-rows, replicated)
        NamedShardings."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as PS

        m = self.mesh
        names = m.axis_names
        d = NamedSharding(m, PS("data")) if "data" in names else None
        c = NamedSharding(m, PS("chains")) if "chains" in names else None
        cd = (NamedSharding(m, PS("chains", "data"))
              if "chains" in names and "data" in names else None)
        return d, c, cd, NamedSharding(m, PS())

    def _place_data(self):
        """X where the step reads it, put there straight from host
        memory: under data="shardmap" each device receives its own rows
        and no device holds the whole matrix."""
        Xs = self.X_global.reshape(self.spec.P, -1, self.D)
        if not self._flat:
            return jnp.asarray(Xs)
        d, _, _, rep = self._shardings()
        if self.spec.data == "shardmap":
            # (N, D) rows over the data axis, replicated over chains
            return jax.device_put(self.X_global, d)
        # chains="mesh" x data="vmap": full (P, N_p, D) copy per chain
        return jax.device_put(Xs, rep)

    @property
    def X(self) -> jax.Array:
        """The (N, D) training rows, on the devices the step reads them
        from."""
        return self._Xn.reshape(self.N, self.D)

    # ---- protocol ---------------------------------------------------------
    def init(self, key: jax.Array | None = None):
        """Fresh (gs, st) from the spec's init knobs; ``key`` defaults to
        ``jax.random.key(spec.seed)``."""
        spec = self.spec
        if key is None:
            key = jax.random.key(spec.seed)
        kw = dict(K_tail=spec.K_tail, alpha=spec.alpha, sigma_x=spec.sigma_x,
                  sigma_a=spec.sigma_a, K_init=spec.K_init)
        Xs = self._Xn.reshape(spec.P, self.N // spec.P, self.D)
        if self.chain_axis:
            gs, ss = init_multichain(key, Xs, spec.n_chains, spec.K_max, **kw)
        else:
            gs, ss = init_hybrid(key, Xs, spec.K_max, **kw)
        return self.place_global(gs), self.from_canonical(ss)

    def step(self, gs, st):
        """One full hybrid iteration (sub-iterations + master sync)."""
        if self._flat:
            gs2, Zf, Zt, ta = self._fns.step(self._Xn, gs, *st)
            return gs2, (Zf, Zt, ta)
        return self._fns.step(self._Xn, gs, st)

    def stale(self, gs, st):
        """One bounded-staleness pass: sub-iterations, no sync (non-exact)."""
        if self._flat:
            gs2, Zf, Zt, ta = self._fns.stale(self._Xn, gs, *st)
            return gs2, (Zf, Zt, ta)
        return self._fns.stale(self._Xn, gs, st)

    def to_canonical(self, st) -> HybridShard:
        """Native state -> canonical (C?, P, N_p, K) HybridShard."""
        if not self._flat:
            return st
        Zf, Zt, ta = st
        spec = self.spec
        P_, N_p = spec.P, self.N // spec.P
        if spec.data == "vmap":       # chains-mesh: already (C, P, N_p, ·)
            return HybridShard(Z=Zf, Z_tail=Zt, tail_active=ta)
        lead = (spec.n_chains,) if self.chain_axis else ()
        return HybridShard(
            Z=Zf.reshape(*lead, P_, N_p, Zf.shape[-1]),
            Z_tail=Zt.reshape(*lead, P_, N_p, Zt.shape[-1]),
            tail_active=ta,
        )

    def place_global(self, gs):
        """The master's state where the step leaves it: on a mesh,
        replicated (sharded over chains under chains="mesh"), so that a
        state from ``init``, from a checkpoint or from ``step`` runs one
        compiled step; as it is off a mesh."""
        if not self._flat:
            return gs
        _, c, _, rep = self._shardings()
        return jax.device_put(gs, c if self.chain_axis else rep)

    def from_canonical(self, ss: HybridShard):
        """Canonical HybridShard -> native device-resident state."""
        if not self._flat:
            return ss
        d, c, cd, _ = self._shardings()
        spec = self.spec
        if spec.data == "vmap":       # chains-mesh, simulated data shards
            return (jax.device_put(ss.Z, c),
                    jax.device_put(ss.Z_tail, c),
                    jax.device_put(ss.tail_active, c))
        *lead, P_, N_p, K = ss.Z.shape
        Kt = ss.Z_tail.shape[-1]
        row = cd if self.chain_axis else d
        return (
            jax.device_put(ss.Z.reshape(*lead, P_ * N_p, K), row),
            jax.device_put(ss.Z_tail.reshape(*lead, P_ * N_p, Kt), row),
            jax.device_put(ss.tail_active, row),
        )


def build_sampler(spec: SamplerSpec, hyp: IBPHypers | None = None,
                  X: Any = None) -> Sampler:
    """THE sampler factory: validated spec + hypers + data -> Sampler.

    Owns everything ``MCMCDriver._build_backend`` used to hand-roll:
    layout selection, mesh construction (with a loud device-count check),
    jit/vmap/shard_map wrapping, data placement, and the canonical <->
    native state conversions that keep checkpoints interchangeable
    across layouts.
    """
    if X is None:
        raise ValueError("build_sampler needs the data matrix X")
    return Sampler(spec, hyp or IBPHypers(), X)
