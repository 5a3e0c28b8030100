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

# the paper's instance and the widest width the kernels are written for
SIZES = {"paper": (1000, 36, 32), "wide": (8192, 1024, 64)}


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
