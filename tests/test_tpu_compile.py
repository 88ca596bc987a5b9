"""Compile rehearsals for a TPU v5e, made without a chip.

The TPU compiler is installed with jaxlib, and it compiles for a chip
that is described but not attached (``get_topology_desc``).  These
tests compile the main path at deployment widths: the histogram kernel
(the one Pallas kernel on the engine path), the batched engine for the
threshold and tree classes, and the sharded engine on a 2x2 mesh.  They
catch what interpret mode cannot see — illegal block shapes, contractions
Mosaic cannot lower, programs that do not fit the chip's memory — at no
chip time.  Nothing here runs; results and times need the chip.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and under
pytest-xdist only the worker that is handed this file may do so.  Keep
every such rehearsal in this one file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.boosting import PRODUCTION_BOOST
from repro.core import batched, sharded_batched, weak
from repro.core.types import BoostConfig
from repro.kernels.histogram import kernel as HK
from repro.kernels.histogram import ops as hist_ops

GiB = 2 ** 30


@pytest.fixture(scope="module")
def topo():
    """The described v5e 2x2.  A described chip's executables cannot be
    read back from JAX's persistent compilation cache (that warns), so
    the cache is off while this module compiles."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            # keep the compiler's logs out of the shared temp directory
            mp.setenv("TPU_LOG_DIR",
                      os.environ.get("TPU_LOG_DIR", "disabled"))
            try:
                desc = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 — any failure: "cannot"
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _engine_args(cfg, B: int, m: int, F: int | None, sharding,
                 shard_players=None):
    """Shapes of one ``_classify_batched_jit``/``_build_sharded`` call:
    (x, y, alive, keys, sched) for B tasks of m points over cfg.k
    players.  ``shard_players`` (a sharding) places the [B, k, …] data
    arrays; everything else gets ``sharding``."""
    k, mloc = cfg.k, m // cfg.k
    data = shard_players or sharding
    x = (_spec((B, k, mloc, F), jnp.float32, data) if F
         else _spec((B, k, mloc), jnp.int32, data))
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), B))
    return (x, _spec((B, k, mloc), jnp.int8, data),
            _spec((B, k, mloc), jnp.bool_, data),
            _spec(keys.shape, keys.dtype, sharding),
            _spec((B, 1, k), jnp.bool_, sharding))


def _tree_deployment():
    """The tree-tenant deployment: F = 28 (Higgs-shaped), Q = 32,
    depth 2, histogram merge, k = 16 players with 512-point coresets."""
    cls = weak.make_class("tree", num_features=28, tree_depth=2,
                          tree_bins=32, tree_comm_mode="histogram")
    cfg = BoostConfig(k=16, coreset_size=512,
                      domain_size=1 << min(cls.value_bits, 30),
                      opt_budget=16, deterministic_coreset=False)
    return cls, cfg


@pytest.mark.parametrize("bins", [32, 64])
@pytest.mark.parametrize("F", [8, 28])
@pytest.mark.parametrize("N", [1, 2, 4])
@pytest.mark.parametrize("form", ["single", "batched"])
def test_histogram_kernel_compiles_for_v5e(one_chip, form, N, F, bins):
    """c = k·coreset = 8192 points; N ∈ {1, 2, 4} nodes are the levels
    of depth-2 and depth-3 trees; batched = the 16 players of one task
    in histogram mode."""
    c = 16 * 512
    lead = (16,) if form == "batched" else ()
    fn = HK.hist_batched_pallas if form == "batched" else HK.hist_pallas
    x = _spec(lead + (c, F), jnp.float32, one_chip)
    w = _spec(lead + (N, c), jnp.float32, one_chip)
    compiled = jax.jit(lambda x, w, wy: fn(x, w, wy, bins=bins)).lower(
        x, w, w).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_thresholds_engine_compiles_for_v5e(one_chip):
    """Threshold tenants at PRODUCTION_BOOST widths (k = 16, coreset
    512, domain 2^20, opt budget 256): B = 32 tasks of m = 2^18.  The
    whole-run program's temporaries stay under 1 GiB (≈ 212 MB when
    this bound was set)."""
    cfg = PRODUCTION_BOOST
    cls = weak.make_class("thresholds", n=cfg.domain_size)
    args = _engine_args(cfg, 32, 1 << 18, None, one_chip)
    t_buf = cfg.num_rounds(1 << 18)
    compiled = batched._classify_batched_jit.lower(
        *args, cfg, cls, t_buf).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 * GiB


def test_tree_engine_compiles_for_v5e_with_the_pallas_kernel(
        one_chip, monkeypatch):
    """Tree tenants, histogram mode, F = 28: B = 8 tasks of m = 2^17.
    The backend check is steered onto the Pallas branch (this process
    runs on the CPU), so the compiled program must contain the kernel.
    Its temporaries (≈ 1.13 GB when this bound was set) stay under
    2 GiB: the quarantine's point match is a sort and a binary search,
    not the [B, m, k·c] compare mask that took 8.9 GB."""
    monkeypatch.setattr(hist_ops, "_on_tpu", lambda: True)
    cls, cfg = _tree_deployment()
    m = 1 << 17
    args = _engine_args(cfg, 8, m, 28, one_chip)
    compiled = batched._classify_batched_jit.lower(
        *args, cfg, cls, cfg.num_rounds(m)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * GiB


def test_sharded_engine_compiles_on_a_2x2_mesh(topo):
    """The players mesh over the four described chips: each holds 4 of
    the k = 16 players, and the per-round exchange is a real
    all-gather.  Threshold tenants at PRODUCTION_BOOST widths."""
    cfg = PRODUCTION_BOOST
    cls = weak.make_class("thresholds", n=cfg.domain_size)
    mesh = sharded_batched.make_players_mesh(cfg.k, devices=topo.devices)
    assert mesh.shape[sharded_batched.AXIS] == 4
    m = 1 << 16
    args = _engine_args(
        cfg, 8, m, None, NamedSharding(mesh, P()),
        shard_players=NamedSharding(mesh, P(None, sharded_batched.AXIS)))
    fn = sharded_batched._build_sharded(mesh, cfg, cls, cfg.num_rounds(m),
                                        False)
    text = fn.lower(*args).compile().as_text()
    assert "all-gather" in text


def test_tree_engine_for_v5e_maps_its_ops_to_steps(one_chip, monkeypatch):
    """The round steps survive the TPU compiler: every device op inside
    the protocol loop of the tree engine compiled for a v5e maps to a
    step, and the histogram kernel's op, found by its name, to
    ``center_erm``.  B = 1 task of 2^12 points at the deployment's
    widths."""
    from repro.obs import trace as obs_trace
    monkeypatch.setattr(hist_ops, "_on_tpu", lambda: True)
    cls, cfg = _tree_deployment()
    m = 1 << 12
    args = _engine_args(cfg, 1, m, 28, one_chip)
    text = batched._classify_batched_jit.lower(
        *args, cfg, cls, cfg.num_rounds(m)).compile().as_text()
    steps = obs_trace.hlo_steps(text)
    kernel = {s for n, s in steps.items()
              if n.startswith("hist_batched_pallas")}
    assert kernel == {"center_erm"}
    assert set(obs_trace.ROUND_STEPS) - set(steps.values()) == {
        "sort_order"}
    comps = obs_trace.parse_hlo(text)
    body = [name for c in re.findall(r"\bbody=%?([\w.\-]+)", text)
            for name, op, *_ in comps[c]
            if op not in ("parameter", "get-tuple-element", "tuple",
                          "constant", "bitcast")]
    assert body and all(steps[n] is not None for n in body)
