"""Collective backend units (PR-12): EQuARX block quantization
(roundtrip properties, wire packing, error bounds — arxiv 2506.17615),
topology model + algorithm selection ("The Big Send-off", arxiv
2504.18658), and the jitted ICI/DCN schedules in
`util.collective.xla` on the virtual 8-device two-slice mesh."""

from __future__ import annotations

import functools

import numpy as np
import pytest

from ray_tpu._internal.config import CONFIG
from ray_tpu.util.collective import quant
from ray_tpu.util.collective.topology import (ALGORITHMS, Topology,
                                              select_algorithm)

RING_MIN = 1 << 16


# ---------------------------------------------------------------------------
# quantization roundtrip properties
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(7,), (64,), (1000, 7), (3, 5, 11),
                                   (1,), (127,), (128,), (129,)])
@pytest.mark.parametrize("block", [1, 3, 64, 256])
def test_quant_roundtrip_error_bound(shape, block):
    """Per-element error <= blockmax/(2*127): the symmetric-int8
    contract, including non-divisible block tails and odd shapes."""
    rng = np.random.RandomState(hash((shape, block)) % (2**31))
    x = (rng.randn(*shape) * rng.uniform(0.01, 100)).astype(np.float32)
    qt = quant.quantize(x, block)
    back = quant.dequantize(qt)
    assert back.shape == x.shape and back.dtype == np.float32
    # per-block bound: |x - dq| <= scale/2 (+1 ulp of slack)
    n = x.size
    nb = -(-n // block)
    assert qt.scales.shape == (nb,)
    flat_err = np.abs(back.ravel() - x.ravel().astype(np.float32))
    per_elem_bound = np.repeat(qt.scales, block)[:n] * 0.5 * 1.001 + 1e-7
    assert (flat_err <= per_elem_bound).all()
    # global gate metric: well under the 1e-2 acceptance bound
    assert quant.max_rel_error(x, back) <= 1.0 / 250


@pytest.mark.parametrize("dtype", [np.float16, np.float32, np.float64])
def test_quant_dtypes_and_pack_roundtrip(dtype):
    rng = np.random.RandomState(3)
    x = rng.randn(513).astype(dtype)  # non-divisible tail at block 64
    qt = quant.quantize(x, 64)
    assert qt.dtype == x.dtype.str
    data = quant.pack(qt)
    assert len(data) == qt.wire_bytes()
    qt2 = quant.unpack(data)
    np.testing.assert_array_equal(qt2.q, qt.q)
    np.testing.assert_array_equal(qt2.scales, qt.scales)
    assert qt2.shape == qt.shape and qt2.dtype == qt.dtype \
        and qt2.block == qt.block
    np.testing.assert_array_equal(quant.dequantize(qt2),
                                  quant.dequantize(qt))


def test_quant_zero_blocks_and_compression():
    x = np.zeros(200, np.float32)
    qt = quant.quantize(x, 64)
    np.testing.assert_array_equal(quant.dequantize(qt), x)
    assert (qt.scales > 0).all()  # no div-by-zero sentinel leaks
    # compression: >= 3.5x fewer bytes than fp32 at block 64
    big = np.random.RandomState(0).randn(1 << 16).astype(np.float32)
    qt = quant.quantize(big, 64)
    assert big.nbytes / qt.wire_bytes() >= 3.5


def test_quant_rejects_bad_block():
    with pytest.raises(ValueError):
        quant.quantize(np.ones(4, np.float32), 0)


def test_quant_jit_matches_numpy_and_caches():
    """The jitted kernels agree with the numpy reference and the
    jitted callable is cached per static config (a fresh jax.jit per
    call would retrace + recompile every time)."""
    rng = np.random.RandomState(5)
    x = rng.randn(300).astype(np.float32)  # non-divisible tail @ 64
    q, scales = quant.quantize_jit(x, 64)
    ref = quant.quantize(x, 64)
    nb = -(-x.size // 64)
    np.testing.assert_array_equal(
        np.asarray(q).ravel()[:x.size], ref.q)
    np.testing.assert_allclose(np.asarray(scales), ref.scales,
                               rtol=1e-6)
    back = quant.dequantize_jit(q, scales, x.size, x.shape)
    np.testing.assert_allclose(np.asarray(back), quant.dequantize(ref),
                               rtol=1e-6, atol=1e-7)
    assert np.asarray(scales).shape == (nb,)
    assert quant._jitted_quantize(64) is quant._jitted_quantize(64)
    assert quant._jitted_dequantize(x.size, x.shape) \
        is quant._jitted_dequantize(x.size, x.shape)


def test_quant_accumulate_wide_error_never_compounds():
    """Summing S dequantized payloads in fp32 bounds the error by S
    single quantizations (the EQuARX 'accumulate wide' property)."""
    rng = np.random.RandomState(11)
    parts = [rng.randn(4096).astype(np.float32) for _ in range(8)]
    exact = np.sum(parts, axis=0, dtype=np.float64)
    acc = np.zeros(4096, np.float64)
    for p in parts:
        acc += quant.dequantize(quant.quantize(p, 64)).astype(np.float64)
    denom = np.abs(exact).max()
    assert np.abs(acc - exact).max() / denom <= 1e-2


# ---------------------------------------------------------------------------
# topology + selector
# ---------------------------------------------------------------------------

def test_topology_constructors_and_queries():
    t = Topology.from_slices(8, 2)
    assert t.num_slices == 2 and t.regular
    assert t.slice_of(0) == 0 and t.slice_of(5) == 1
    assert t.members(1) == (4, 5, 6, 7)
    assert t.peer_group(1) == (1, 5)
    flat = Topology.flat(4)
    assert flat.num_slices == 1 and flat.regular
    b = Topology.from_bundle_nodes(["n0", "n1", "n0", "n1"])
    assert b.num_slices == 2 and b.slices == ((0, 2), (1, 3))
    assert not Topology(3, ((0,), (1, 2))).regular
    with pytest.raises(ValueError):
        Topology.from_slices(8, 3)
    with pytest.raises(ValueError):
        Topology(4, ((0, 1), (1, 2)))  # rank 1 twice, 3 missing


def test_topology_from_mesh_config():
    from ray_tpu.parallel import MeshConfig
    cfg = MeshConfig(data=2, fsdp=2, tensor=2, dcn_axes=("data",))
    t = Topology.from_mesh_config(cfg, 8)
    assert t.num_slices == 2
    assert Topology.from_mesh_config(MeshConfig(data=2, tensor=4),
                                     8).num_slices == 1
    # host_topology: the MeshConfig-side hook
    assert cfg.host_topology(4).slices == ((0, 1), (2, 3))
    with pytest.raises(ValueError):
        MeshConfig(data=-1, dcn_axes=("data",)).host_topology(4)


def test_selector_flat_matches_legacy_cutover():
    """Degenerate 1-slice topology under auto falls back to the exact
    pre-backend star/ring regimes."""
    flat = Topology.flat(8)
    assert select_algorithm(RING_MIN, flat, 8,
                            ring_min_bytes=RING_MIN) == "ring"
    assert select_algorithm(RING_MIN - 1, flat, 8,
                            ring_min_bytes=RING_MIN) == "star"
    # world < 3 never rings (the legacy guard)
    assert select_algorithm(RING_MIN * 4, Topology.flat(2), 2,
                            ring_min_bytes=RING_MIN) == "star"
    # topology omitted entirely = flat
    assert select_algorithm(RING_MIN * 4, None, 8,
                            ring_min_bytes=RING_MIN) == "ring"


def test_selector_multislice_regimes():
    t = Topology.from_slices(8, 2)
    assert select_algorithm(RING_MIN, t, 8,
                            ring_min_bytes=RING_MIN) == "hier"
    assert select_algorithm(RING_MIN - 1, t, 8,
                            ring_min_bytes=RING_MIN) == "tree"


def test_selector_forcing_and_validation():
    t = Topology.from_slices(8, 2)
    for algo in ("ring", "tree", "hier", "star"):
        assert select_algorithm(1, t, 8, ring_min_bytes=RING_MIN,
                                forced=algo) == algo
    # forced hier on an irregular topology degrades to ring, not a hang
    irregular = Topology(3, ((0,), (1, 2)))
    assert select_algorithm(1 << 20, irregular, 3,
                            ring_min_bytes=RING_MIN,
                            forced="hier") == "ring"
    with pytest.raises(ValueError):
        select_algorithm(1, t, 8, ring_min_bytes=RING_MIN,
                         forced="bogus")
    assert "auto" in ALGORITHMS


def test_selector_reads_config_flag():
    prior = CONFIG.collective_algo
    try:
        CONFIG.apply_system_config({"collective_algo": "tree"})
        assert select_algorithm(1 << 20, Topology.flat(8), 8,
                                ring_min_bytes=RING_MIN) == "tree"
    finally:
        CONFIG.apply_system_config({"collective_algo": prior})


def test_collective_flags_registered():
    # L003 contract: every flag resolves against _DEFAULTS
    assert CONFIG.collective_algo == "auto"
    assert CONFIG.collective_quant == "off"
    assert CONFIG.collective_quant_block == 64
    assert CONFIG.lease_reclaim_delay_s > 0


# ---------------------------------------------------------------------------
# jitted schedules on the virtual two-slice mesh
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def two_slice_mesh():
    import jax
    from ray_tpu.parallel import MeshConfig
    devices = jax.devices()[:8]
    if len(devices) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    cfg = MeshConfig(data=2, fsdp=2, tensor=2, dcn_axes=("data",))
    return cfg.build(devices)


def _psum_ref(x, mesh, axes):
    import jax
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    spec = P(("data", "fsdp"))

    @functools.partial(shard_map, mesh=mesh, in_specs=(spec,),
                       out_specs=spec, check_vma=False)
    def _ar(blk):
        return jax.lax.psum(blk, axes)

    return jax.jit(_ar)(x)


def test_xla_hierarchical_allreduce_matches_psum(two_slice_mesh):
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from ray_tpu.util.collective import xla
    x = jnp.asarray(np.random.RandomState(0).randn(16, 64)
                    .astype(np.float32))
    spec = P(("data", "fsdp"))
    h = xla.hierarchical_allreduce(x, two_slice_mesh, ici_axis="fsdp",
                                   dcn_axis="data", in_spec=spec)
    ref = _psum_ref(x, two_slice_mesh, ("data", "fsdp"))
    np.testing.assert_allclose(np.asarray(h), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_xla_quantized_allreduce_error_gate(two_slice_mesh):
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from ray_tpu.util.collective import xla
    x = jnp.asarray(np.random.RandomState(1).randn(16, 64)
                    .astype(np.float32))
    spec = P(("data", "fsdp"))
    q = xla.quantized_allreduce(x, two_slice_mesh, "data", block=64,
                                in_spec=spec)
    ref = _psum_ref(x, two_slice_mesh, "data")
    err = float(np.abs(np.asarray(q) - np.asarray(ref)).max()
                / np.abs(np.asarray(ref)).max())
    assert err <= 1e-2, err


def test_xla_hier_quantized_allreduce_error_gate(two_slice_mesh):
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from ray_tpu.util.collective import xla
    x = jnp.asarray(np.random.RandomState(2).randn(16, 64)
                    .astype(np.float32))
    spec = P(("data", "fsdp"))
    hq = xla.hierarchical_quantized_allreduce(
        x, two_slice_mesh, ici_axis="fsdp", dcn_axis="data", block=64,
        in_spec=spec)
    ref = _psum_ref(x, two_slice_mesh, ("data", "fsdp"))
    err = float(np.abs(np.asarray(hq) - np.asarray(ref)).max()
                / np.abs(np.asarray(ref)).max())
    assert err <= 1e-2, err


def test_dryrun_dcn_quant_grad_ab_gates():
    """The two-slice dryrun's quantized-DCN arm: slice-local backward,
    int8 DCN combine, post-update loss parity + byte-ratio gates."""
    import jax

    import __graft_entry__ as graft
    out = graft._dcn_quant_grad_ab(jax.devices()[:8])
    assert out, "quant A/B skipped on the 8-device mesh"
    assert out["ratio"] >= 3.5
    assert out["max_err"] <= 1e-2
    exact, int8 = out["losses"]["exact"], out["losses"]["int8"]
    assert abs(int8 - exact) <= 1e-2 * abs(exact)
