"""Compile every Pallas kernel for a described (not attached) TPU v5e
chip, at the widths the main path runs them, and check that each one
lowers to a Mosaic kernel (``tpu_custom_call``).

Interpret-mode tests (test_kernels.py) cannot see what the TPU compiler
refuses: block shapes that do not tile (8, 128), rank-1 blocks, VMEM
overflow. The topology is described inside a module fixture, never at
import, so every xdist worker collects the same tests and only the one
given this file loads the TPU compiler. The persistent compilation
cache is off while these compile: entries written for a described chip
cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import flash_decode as fd
from repro.kernels import kmeans_assign as km
from repro.kernels import mem_attention as ma
from repro.kernels import weighted_agg as wa
from repro.launch.serve_split import SplitLMConfig

F32, I32 = jnp.float32, jnp.int32
LM = SplitLMConfig()
# the benchmark population's seven group sizes (100 clients round-robin
# over the paper's seven device profiles)
PAPER_OWNERS = (15, 15, 14, 14, 14, 14, 14)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    if prev_log is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = prev_log


# name -> (kernel called with interpret=False, argument shapes/dtypes)
CASES = {
    # Eq.-16 round at the paper population, one leaf in its stored
    # layout: G layer 1's conv weight over its six owner groups, G
    # layer 0's dense weight (a ragged 110-row view) and its bias over
    # all seven, six cluster rows each
    "clustered_agg_paper": (
        lambda *a: wa.clustered_agg(a[:6], a[6:], interpret=False),
        [((6, r), F32) for r in PAPER_OWNERS[1:]]
        + [((r, 4096, 128), F32) for r in PAPER_OWNERS[1:]]),
    "clustered_agg_paper_dense": (
        lambda *a: wa.clustered_agg(a[:7], a[7:], interpret=False),
        [((6, r), F32) for r in PAPER_OWNERS]
        + [((r, 110, 12544), F32) for r in PAPER_OWNERS]),
    "clustered_agg_paper_bias": (
        lambda *a: wa.clustered_agg(a[:7], a[7:], interpret=False),
        [((6, r), F32) for r in PAPER_OWNERS]
        + [((r, 12544), F32) for r in PAPER_OWNERS]),
    "weighted_agg_paper": (
        lambda x, w: wa.weighted_agg_flat(x, w, interpret=False),
        [((100, 2 ** 20), F32), ((100,), F32)]),
    # stage-3 assignment: 100 clients x the 7*7*128 middle activation
    "kmeans_assign_paper": (
        lambda x, c: km.kmeans_assign(x, c, interpret=False),
        [((100, 6272), F32), ((4, 6272), F32)]),
    # split-LM decode tail (SplitLMConfig): server-trunk decode and
    # prefill kernels at the tail's head counts and widths
    "flash_decode_lm_tail": (
        lambda q, k, v, n: fd.flash_decode(q, k, v, n, interpret=False),
        [((3, LM.n_heads, LM.head_dim), F32),
         ((3, LM.s_max, LM.n_kv, LM.head_dim), F32),
         ((3, LM.s_max, LM.n_kv, LM.head_dim), F32), ((), I32)]),
    "mem_attention_lm_tail": (
        lambda q, k, v, n: ma.mem_attention(q, k, v, n, interpret=False),
        [((3, 40, LM.n_heads, LM.head_dim), F32),
         ((3, 40, LM.n_kv, LM.head_dim), F32),
         ((3, 40, LM.n_kv, LM.head_dim), F32), ((3,), I32)]),
    # 128-wide heads over a long cache / prompt: the tile budget and
    # the head-major layout at MXU-sized blocks
    "flash_decode_hd128": (
        lambda q, k, v, n: fd.flash_decode(q, k, v, n, interpret=False),
        [((4, 32, 128), F32), ((4, 4096, 8, 128), F32),
         ((4, 4096, 8, 128), F32), ((), I32)]),
    "mem_attention_hd128": (
        lambda q, k, v, n: ma.mem_attention(q, k, v, n, interpret=False),
        [((2, 512, 8, 128), F32), ((2, 512, 2, 128), F32),
         ((2, 512, 2, 128), F32), ((2,), I32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("net", ["G", "D"])
def test_paper100_round_compiles_for_v5e(one_chip, net, monkeypatch):
    """The device-weights aggregation program at the benchmark's 100
    clients compiles with the Pallas kernel for its lane-dense leaves,
    the leaves stay in their layout (no array beyond twice the largest
    group's stacked leaf), and the outputs alias the donated inputs."""
    import re

    import numpy as np
    from repro.core.federation import FederationPlan
    from repro.kernels import ops
    from test_federation_fused import paper_template_shapes
    # the kernel wrappers pick interpret mode from the process's backend
    # (the CPU here); compile them as the chip would
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    groups, template = paper_template_shapes(net, sharding=one_chip)
    plan = FederationPlan(groups, net, 5, template)
    fn = plan._make_agg_device_fn(6, True, donate=True)
    compiled = fn.lower(
        template, jax.ShapeDtypeStruct((100,), F32, sharding=one_chip),
        jax.ShapeDtypeStruct((100,), I32, sharding=one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    largest = max(x.size for x in jax.tree_util.tree_leaves(template))
    sizes = [int(np.prod([int(d) for d in dims.split(",")]))
             for dims in re.findall(r"f32\[([0-9,]+)\]", text)]
    assert max(sizes) <= 2 * largest
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= mem.output_size_in_bytes - 2 ** 20
