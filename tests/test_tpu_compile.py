"""Compile the device paths for a described TPU v5e, without the chip.

The TPU compiler refuses what interpret mode accepts: lane-axis slices it
cannot prove aligned, integer argmin, and more scoped VMEM than the limit.
These tests compile the fused dispatch kernel at cluster widths, the
dispatch wrapper's whole-call program at the benchmark's shape, and the
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
from repro.core import jax_sched
from repro.kernels import sched_step
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
    # the form's choice refuses a non-TPU default backend; here the target
    # is the described chip, not the backend this process runs on
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    R, F = jax_sched.CHUNK, 40
    table = sched_step.table_form("test", (F, n_workers), interpret=False)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32, sharding=one_chip)
    compiled = (
        sched_step.launch
        .lower(i32((R,)), i32((R,)), i32((R,)), i32((F, n_workers)), i32((n_workers,)),
               interpret=False, table=table)
        .compile()
    )
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the chip benchmark finds the kernel in the device trace by this name
    assert re.search(r"%_sched_events(\.\d+)? = .*custom-call\(", text)
    assert compiled.memory_analysis().argument_size_in_bytes >= F * n_workers * 4


@pytest.mark.parametrize("n_events", [1, 35])
def test_sched_many_fused_call_compiles_for_v5e(one_chip, n_events):
    """``sched_many_fused``'s whole-call program at the chip benchmark's
    shape, the paper's 40 functions x 5 workers, for bursts of 1 and 35
    events: the kernel keeps its ``_sched_events`` name inside it."""
    F, W = 40, 5
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32, sharding=one_chip)
    compiled = (
        jax_sched._fused_call
        .lower(i32((n_events, 3)), i32((F, W)), i32((W,)), chunk=jax_sched.CHUNK, interpret=False,
               table="vmem")
        .compile()
    )
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the chip benchmark finds the kernel in the device trace by this name
    assert re.search(r"%_sched_events(\.\d+)? = .*custom-call\(", text)


def _no_table_copy(compiled, F, W):
    """The table is read and written by the kernel alone: it enters and
    leaves the program in one layout, no op outside the kernel makes a
    table-sized copy, pad, slice or transpose of it or of its worker-major
    view (those are bitcasts), and the program keeps less than one table's
    bytes of temporaries."""
    text = compiled.as_text()
    assert re.search(r"%_sched_events_hbm(\.\d+)? = .*custom-call\(", text)
    table_ops = [line for line in text.splitlines()
                 if re.search(rf"= s32\[({F},{W}|{W},{F})\]\S* (copy|pad|slice|transpose|fusion)",
                              line)]
    assert not table_ops
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * F * W


def test_sched_events_hbm_compiles_for_v5e(one_chip):
    """The HBM form of the dispatch kernel at the published 8,192 functions
    x 5,000 workers, the table in its default layout: the kernel's
    worker-major view of it is that layout, and the ragged edge (5,000 is no
    multiple of 128) is taken as it is.  It keeps at least 12 column blocks
    resident, where the chip benchmark's traffic on this table finds nearly
    all the hits that keeping every block would."""
    R, F, W = jax_sched.CHUNK, 8192, 5000
    assert sched_step.hbm_slots(F, W) >= 12
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32, sharding=one_chip)
    kernel = jax.jit(functools.partial(sched_step.sched_events_hbm, interpret=False))
    compiled = kernel.lower(i32((R,)), i32((R,)), i32((R,)), i32((F, W)), i32((1, W))).compile()
    _no_table_copy(compiled, F, W)
    assert compiled.input_formats[0][3].layout == compiled.output_formats[2].layout


def test_sched_events_hbm_compiles_at_its_widest_for_v5e(one_chip):
    """The HBM form at the widest table ``table_form`` lets it take, where
    one resident column block is all that fits beside the staging and
    spill blocks, compiles within the VMEM it asks for."""
    R, F = jax_sched.CHUNK, 256
    W = next(w for w in range(5000, 10**6, 128)
             if sched_step.hbm_vmem_bytes(w + 128) > sched_step.VMEM_CAP_BYTES)
    assert sched_step.table_form("t", (F, W), interpret=True) == "hbm"
    assert sched_step.hbm_slots(F, W) == 1
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32, sharding=one_chip)
    kernel = jax.jit(functools.partial(sched_step.sched_events_hbm, interpret=False))
    kernel.lower(i32((R,)), i32((R,)), i32((R,)), i32((F, W)), i32((1, W))).compile()


@pytest.mark.parametrize("n_events", [534, 1100])
def test_sched_many_fused_hbm_call_compiles_for_v5e(one_chip, n_events):
    """``sched_many_fused``'s whole-call program on the HBM form at the
    published 8,192 x 5,000, for the benchmark's median burst (one chunk)
    and one of two chunks (the second updates the first's table in
    place): one kernel a chunk and no table copy outside it, the table in
    and out in the layout a fresh array of its shape has, so the
    persistent compile cache, which keeps only that layout, serves it."""
    F, W = 8192, 5000
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32, sharding=one_chip)
    compiled = (
        jax_sched._fused_call
        .lower(i32((n_events, 3)), i32((F, W)), i32((W,)), chunk=jax_sched.CHUNK, interpret=False,
               table="hbm")
        .compile()
    )
    _no_table_copy(compiled, F, W)
    launches = re.findall(r"%_sched_events_hbm(\.\d+)? = ", compiled.as_text())
    assert len(launches) == -(-n_events // jax_sched.CHUNK)
    default = jax.jit(lambda x: x + 1).lower(i32((F, W))).compile()
    assert compiled.input_formats[0][1].layout == default.input_formats[0][0].layout
    assert compiled.output_formats[0].layout == default.input_formats[0][0].layout


def _launch_lengths(text, kernel):
    """The events each launch of ``kernel`` in a compiled program runs:
    the length of its three ``s32[R]`` event operands, in sorted order."""
    return sorted(int(r) for r in re.findall(
        rf"%{kernel}(?:\.\d+)? = .*custom-call\(.*operand_layout_constraints="
        r"\{s32\[(\d+)\]\{0\}, s32\[\1\]\{0\}, s32\[\1\]\{0\}, ", text))


@pytest.mark.parametrize("table,n_events,launches", [
    ("vmem", 1, [1]), ("vmem", 35, [35]), ("hbm", 534, [534]), ("hbm", 1100, [76, 1024]),
], ids=["vmem-1", "vmem-35", "hbm-534", "hbm-1100"])
def test_sched_many_fused_launches_are_sized_to_the_burst(one_chip, table, n_events, launches):
    """``sched_many_fused``'s whole-call program launches the kernel on
    exactly the events it is given, at 1,024-event chunks: a burst shorter
    than a chunk is one launch of its own length, with no array of a
    chunk's length anywhere (nothing pads the events), and a longer one a
    launch per whole chunk and one for the tail, the HBM form's tail
    updating the table in place (no table copy outside the kernels)."""
    F, W = (40, 5) if table == "vmem" else (8192, 5000)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32, sharding=one_chip)
    compiled = (
        jax_sched._fused_call
        .lower(i32((n_events, 3)), i32((F, W)), i32((W,)), chunk=jax_sched.CHUNK, interpret=False,
               table=table)
        .compile()
    )
    text = compiled.as_text()
    kernel = "_sched_events" if table == "vmem" else "_sched_events_hbm"
    assert _launch_lengths(text, kernel) == launches
    if n_events < 1024:
        assert not re.search(r"s32\[1024[,\]]", text)
    if table == "hbm":
        _no_table_copy(compiled, F, W)


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
