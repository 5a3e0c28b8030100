"""The four Pallas kernels compile for a described TPU v5e chip.

Interpret mode runs the kernel bodies on the CPU and cannot see what the
TPU compiler refuses: scalar stores to VMEM, blocks not aligned to the
tiling, more fast memory than a kernel may use. Each case compiles one
kernel with ``interpret=False`` for one chip of a described ``v5e:2x2``
and checks that the compiled program holds the kernel. Nothing runs.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
tests run under several workers.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.collapsed_row.kernel import collapsed_row_flip_pallas
from repro.kernels.feature_stats import feature_stats_core
from repro.kernels.gaussian_sse import gaussian_sse_core
from repro.kernels.gibbs_flip import gibbs_flip_core
from repro.kernels.tail_scan import tail_scan_pallas

# the paper's instance and the widest width the kernels are written for
SIZES = {"paper": (1000, 36, 32), "wide": (8192, 1024, 64)}
# the fused tail's rows on p′: the paper's instance, the four-chip fit,
# and a million rows over four chips (the row-block grid keeps VMEM
# bounded whatever the rows)
TAIL_ROWS = {"paper": 180, "dp4": 2250, "million": 250_000}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A program compiled for a described chip is written to the
    persistent cache but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _lower(fn, shapes, sharding):
    f32 = jnp.float32
    args = [jax.ShapeDtypeStruct(s, f32, sharding=sharding) for s in shapes]
    return jax.jit(fn).lower(*args)


def _kernel_case(name, N, D, K):
    """(function of f32 arrays, their shapes) for one kernel."""
    if name == "gibbs_flip":
        fn = lambda X, Z, A, lpi, act, u, s: gibbs_flip_core(
            X, Z, A, lpi, act, u, s, interpret=False)
        return fn, [(N, D), (N, K), (K, D), (K,), (K,), (N, K), ()]
    if name == "feature_stats":
        fn = lambda X, Z: feature_stats_core(X, Z, interpret=False)
        return fn, [(N, D), (N, K)]
    if name == "gaussian_sse":
        fn = lambda X, Z, A, act: gaussian_sse_core(X, Z, A, act,
                                                    interpret=False)
        return fn, [(N, D), (N, K), (K, D), (K,)]
    fn = lambda *a: collapsed_row_flip_pallas(*a, interpret=False)
    return fn, [(K, K), (K, D), (D,), (K,), (K,), (), (D,), (K,), (K,),
                (K,), (), ()]


@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("name", ["gibbs_flip", "feature_stats",
                                  "gaussian_sse", "collapsed_row"])
def test_kernel_compiles_for_v5e(name, size, one_chip):
    fn, shapes = _kernel_case(name, *SIZES[size])
    compiled = _lower(fn, shapes, one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_sweep_carries_its_scope_for_v5e(one_chip, monkeypatch):
    """The uncollapsed sweep's ``ibp_sweep`` scope (DESIGN.md §16) reaches
    the compiled gibbs_flip custom call, not only the jnp sweep's ops."""
    import re

    from repro.core.ibp.sweeps import uncollapsed_sweep
    from repro.kernels.gibbs_flip import ops as gf_ops

    # compile the kernel, not its interpreter, for the described chip
    monkeypatch.setattr(gf_ops, "default_interpret", lambda: False)
    N, D, K = SIZES["paper"]
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)

    def sweep(X, Z, A, pi, active, sigma_x, key):
        return uncollapsed_sweep(X, Z, A, pi, active, sigma_x, key,
                                 backend="pallas")

    f32 = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
           for s in [(N, D), (N, K), (K, D), (K,), (K,), ()]]
    hlo = jax.jit(sweep).lower(*f32, key).compile().as_text()
    calls = re.findall(r"= [^\n]*? custom-call\([^\n]*?"
                       r"custom_call_target=\"tpu_custom_call\"[^\n]*?"
                       r"op_name=\"([^\"]*)\"", hlo)
    assert calls and all("ibp_sweep/" in c for c in calls), calls


@pytest.mark.parametrize("rows", sorted(TAIL_ROWS))
def test_tail_scan_compiles_for_v5e(rows, one_chip):
    n, K, D = TAIL_ROWS[rows], 8, 36
    fn = lambda *a: tail_scan_pallas(*a, N=4.0 * n, refresh_every=64,
                                     drift_tol=1e-2, interpret=False)
    shapes = [(n, K), (K,), (K, K), (K, D), (K,), (n, D), (n, K), (n,),
              (n,), (), ()]
    compiled = _lower(fn, shapes, one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_tail_carries_its_scope_for_v5e(one_chip, monkeypatch):
    """On a TPU the hybrid tail takes the fused path: its row scan is
    one ``ibp_tail_scan`` custom call, inside the ``ibp_tail`` scope."""
    import re

    from repro.core.ibp import collapsed, hybrid

    monkeypatch.setattr(collapsed, "on_tpu", lambda: True)
    monkeypatch.setattr(collapsed, "default_interpret", lambda: False)
    n, D, K_max, K_tail = 180, 36, 32, 8
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    key = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    gs = hybrid.HybridGlobal(
        A=f32(K_max, D), pi=f32(K_max), active=f32(K_max), alpha=f32(),
        sigma_x=f32(), sigma_a=f32(), key=key, p_prime=i32, it=i32,
        overflow=i32, tail_sat=i32, tail_refresh=i32)

    def tail(X_p, Z, Z_tail, tail_active, gs, key):
        return hybrid._tail_sub_iteration(
            X_p, Z, Z_tail, tail_active, gs, 900.0, key,
            collapsed_backend="fast", chol_refresh=64, k_live_pack=True)

    hlo = jax.jit(tail).lower(f32(n, D), f32(n, K_max), f32(n, K_tail),
                              f32(K_tail), gs, key).compile().as_text()
    calls = re.findall(r"= [^\n]*? custom-call\([^\n]*?"
                       r"custom_call_target=\"tpu_custom_call\"[^\n]*?"
                       r"op_name=\"([^\"]*)\"", hlo)
    assert len(calls) == 1 and "ibp_tail/" in calls[0], calls
    assert "ibp_tail_scan" in hlo
