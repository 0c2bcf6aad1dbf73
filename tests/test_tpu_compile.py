"""Compile the device paths for a described TPU v5e, without the chip.

The TPU compiler refuses what interpret mode accepts: lane-axis slices it
cannot prove aligned, integer argmin, and more scoped VMEM than the limit.
These tests compile the fused dispatch kernel at cluster widths and the
full-width served model's steps for one v5e chip, so such faults show here
at no chip time.  Nothing runs; a compile that passes is not a chip run.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import build_model, unzip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        # the TPU library reads this when it loads; unset, its compiler logs
        # go to a fixed directory shared by every checkout on the machine
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler / library lock held elsewhere
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # an executable compiled for a described chip cannot be read back from
        # the persistent cache without one: keep this module's compiles out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)


def _spec(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree
    )


@pytest.mark.parametrize("n_workers", [1600, 100_000])
def test_sched_events_compiles_for_v5e(one_chip, n_workers, monkeypatch):
    """The fused dispatch kernel at 40 functions x 1,024-event chunks, at the
    1,600-worker anchor and the 100k-worker cluster (which needs the raised
    scoped-VMEM limit)."""
    # ops.sched_events refuses a non-TPU default backend; here the target
    # is the described chip, not the backend this process runs on
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    R, F = 1024, 40
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32, sharding=one_chip)
    compiled = (
        jax.jit(functools.partial(ops.sched_events, interpret=False))
        .lower(i32((R,)), i32((R,)), i32((R,)), i32((F, n_workers)), i32((n_workers,)))
        .compile()
    )
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the chip benchmark finds the kernel in the device trace by this name
    assert re.search(r"%_sched_events(\.\d+)? = .*custom-call\(", text)
    assert compiled.memory_analysis().argument_size_in_bytes >= F * n_workers * 4


@pytest.mark.parametrize("step", ["prefill", "decode_step"])
def test_mamba2_130m_serve_step_compiles_for_v5e(one_chip, step):
    """The served endpoint at its published widths, as ``serving.Instance``
    builds it: f32 params, 128-token cache, a batch of one 8-token prompt."""
    cfg = get_config("mamba2_130m")
    model = build_model(cfg, param_dtype=jnp.float32, remat=False)
    params = _spec(
        jax.eval_shape(lambda k: unzip(model.init(k, max_seq=128))[0], jax.random.key(0)),
        one_chip,
    )
    if step == "prefill":
        args = ({"tokens": jax.ShapeDtypeStruct((1, 8), jnp.int32, sharding=one_chip)},)
    else:
        cache = jax.eval_shape(lambda: model.init_cache(1, 128, dtype=jnp.float32, memory_t=8))
        args = (
            jax.ShapeDtypeStruct((1, 1), jnp.int32, sharding=one_chip),
            _spec(cache, one_chip),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        )
    compiled = jax.jit(getattr(model, step)).lower(params, *args).compile()
    n_params = sum(x.size for x in jax.tree.leaves(params))
    assert abs(n_params - cfg.n_params()) < 0.01 * cfg.n_params()  # full width
    assert compiled.memory_analysis().argument_size_in_bytes >= 4 * n_params
