"""What the chip's compiler accepts, asked without the chip.

The main path's device programs are compiled ahead of time for a
*described* TPU v5e (jax.experimental.topologies) at the shapes the node
dispatches: the resident miner's h7 sweep, the Pallas sweep, the w4 Pallas
verify kernel and (slow-marked: minutes each) the fused GLV verify buckets.
Nothing runs — a passing compile is not a chip run — but a kernel the
compiler refuses fails here instead of becoming a silent rung down on the
chip (the looped field form below is exactly that: it lowers on the CPU
and Mosaic refuses its dynamic_slice).

All in ONE file, topology described inside a fixture: the worker that gets
this file loads the TPU library and keeps its lock; no other may. The
kernel forms hang on ops/sha256.backend_is_cpu(), which sees the CPU here,
so the tests steer them with the existing BCP_SECP_PARALLEL /
BCP_SHA_UNROLL switches and clear jax's trace caches (module-level jits
would otherwise reuse a trace made under the other form).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever stops the describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """An AOT compile for a described chip is written to the persistent
    cache but cannot be read back without the chip (the next one warns and
    recompiles): keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


@pytest.fixture
def chip_forms(monkeypatch, no_persistent_cache):
    """Trace the accelerator forms, from a clean trace cache."""
    monkeypatch.setenv("BCP_SECP_PARALLEL", "1")
    monkeypatch.setenv("BCP_SHA_UNROLL", "1")
    jax.clear_caches()
    yield
    jax.clear_caches()


def _sweep_args(one_chip):
    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one_chip)

    return s((8,)), s((3,)), s(()), s(()), s(())


def _verify_args(one_chip, bucket: int):
    m = jax.ShapeDtypeStruct((bucket, 32), jnp.uint8, sharding=one_chip)
    v = jax.ShapeDtypeStruct((bucket,), jnp.uint8, sharding=one_chip)
    return m, m, m, m, v, m, m, v


def _ladder_args(one_chip, bucket: int):
    """What _glv_prepare_program hands _glv_dev_program, as shapes."""
    from bitcoincashplus_tpu.ops import secp256k1 as dev

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(dev._glv_prepare_program,
                       *_verify_args(one_chip, bucket)))


def test_resident_sweep_compiles_for_v5e(one_chip, chip_forms):
    """mining/resident's program: sweep_fast_jit at the node's TPU tile."""
    from bitcoincashplus_tpu.ops.sha256_sweep import sweep_fast_jit

    compiled = sweep_fast_jit.lower(*_sweep_args(one_chip),
                                    tile=1 << 16).compile()
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0


@pytest.mark.parametrize("module,name,target_shape", [
    ("sha256_sweep", "sweep_fast_jit", ()), ("miner", "sweep_jit", (8,))])
def test_sweep_lowers_unrolled_for_v5e(one_chip, chip_forms, module, name,
                                       target_shape):
    """Both sweep programs keep their unrolled, hoisted per-nonce form for
    the chip: the tile loop is the lowered text's only loop. The looped
    compress that ops/miner._sweep_tile picks on the CPU backend would
    bring two more. (PR 25 held the whole text of both, at tiles 2^16 and
    2^12, equal before and after it: CHANGES.md.)"""
    import importlib

    program = getattr(
        importlib.import_module(f"bitcoincashplus_tpu.ops.{module}"), name)
    mid, tail, _, start, n_tiles = _sweep_args(one_chip)
    target = jax.ShapeDtypeStruct(target_shape, jnp.uint32,
                                  sharding=one_chip)
    text = program.lower(mid, tail, target, start, n_tiles,
                         tile=1 << 16).as_text()
    assert text.count("stablehlo.while") == 1


def test_pallas_sweep_compiles_for_v5e(one_chip, chip_forms):
    from bitcoincashplus_tpu.ops.pallas_sweep import pallas_sweep_jit

    compiled = pallas_sweep_jit.lower(*_sweep_args(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_w4_verify_kernel_compiles_for_v5e(one_chip, chip_forms):
    """The fallback rung's Pallas kernel, accelerator (parallel) form."""
    from bitcoincashplus_tpu.ops import secp256k1 as dev

    compiled = dev._w4_bytes_program.lower(
        *_verify_args(one_chip, 1024)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_w4_looped_form_is_refused_by_mosaic(one_chip, chip_forms,
                                             monkeypatch):
    """The form the CPU environment picks does not lower for the chip —
    why the form must follow the backend JAX really runs on, and why a
    refusal under -tpu=1 is fatal instead of a rung down."""
    from bitcoincashplus_tpu.ops import secp256k1 as dev

    monkeypatch.setenv("BCP_SECP_PARALLEL", "0")
    jax.clear_caches()
    with pytest.raises(NotImplementedError, match="dynamic_slice"):
        dev._w4_bytes_program.lower(*_verify_args(one_chip, 1024)).compile()


def test_glv_window_step_lowers_without_gather_for_v5e(one_chip, chip_forms):
    """One ladder window in the chip's form reads its per-lane Q and
    lambda-Q tables with selects: on the chip a per-lane gather out of a
    stacked (16, 20, B) table fetches a word at a time, 2.0 ms a
    coordinate at B = 8192, six a window, three quarters of the program
    (PR 29). Lowered only, not compiled: the whole program is the slow
    test below."""
    from bitcoincashplus_tpu.ops import secp256k1 as dev

    lanes = 8192

    def s(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    limbs = s(jnp.uint32, dev.N_LIMBS, lanes)
    mask = s(jnp.int32, 1, lanes)
    acc = {"X": limbs, "Y": limbs, "Z": limbs, "inf": mask}
    table = (s(jnp.uint32, 16, dev.N_LIMBS, lanes),) * 3
    text = jax.jit(dev._glv_window_step).lower(
        (acc, mask), mask, mask, table, table, mask).as_text()
    assert "stablehlo.select" in text
    assert "stablehlo.gather" not in text


@pytest.mark.parametrize("op,ceiling", [("f_mul", 26), ("f_carry_sub", 12)])
def test_field_op_launch_count_for_v5e(one_chip, chip_forms, op, ceiling):
    """The ladder's time on the chip follows its launches (0.63 us each,
    137k a dispatch until PR 33), and nine in ten of them were the field
    normaliser's carry rounds. Compiled for the chip at the node's bucket,
    a multiplication is 24 fusions (4 the schoolbook, 20 the product
    normaliser; 37 before PR 33) and a normalised difference 10 (one round,
    one fold, the weaken; 32 before): an edit that grows the normaliser
    again fails here and not on the chip."""
    from bitcoincashplus_tpu.ops import secp256k1 as dev

    limbs = jax.ShapeDtypeStruct((dev.N_LIMBS, 8192), jnp.uint32,
                                 sharding=one_chip)
    text = jax.jit(getattr(dev, op)).lower(limbs, limbs).compile().as_text()
    assert 0 < text.count(" fusion(") <= ceiling


def test_glv_ladder_program_takes_its_tables_for_v5e(one_chip, chip_forms):
    """The program that runs the ladder is handed every operand of the
    loop: the six stacked (16, 20, B) tables are arguments of its main,
    none is stacked inside it, and the only loops in it are the ladder and
    the comb. With the tables built in front of the loop in ONE program
    the chip ran the loop's own fusions 2x slower (2.00 ms a window
    against 1.03; PERF.md §6, PR 39), and nothing in the compiled text
    shows it: the property is held here, in the lowered text."""
    from bitcoincashplus_tpu.ops import secp256k1 as dev

    lanes = 8192
    text = dev._glv_dev_program.lower(
        *_ladder_args(one_chip, lanes)).as_text()
    table = f"tensor<16x{dev.N_LIMBS}x{lanes}xui32>"
    main = next(ln for ln in text.splitlines()
                if "func.func public @main" in ln)
    assert main.count(table) == 6
    assert not [ln for ln in text.splitlines()
                if "stablehlo.concatenate" in ln
                and ln.rstrip().endswith(table)]
    assert text.count("stablehlo.while") == 2
    # PR 44 put a Schnorr program beside this one and split a helper out
    # of _glv_comb_final: the ECDSA program's lowered text is what it was
    # at PR 43 (letter for letter; the chip's warm cache is keyed on it)
    assert _digest(text) == ECDSA_LOWERED["_glv_dev_program"]


# sha256 of the text each ECDSA stage lowers to for a described v5e at the
# node's bucket of 8,192 lanes, chip forms, as of PR 43 (2428f59): an edit
# that moves either compiles both anew on every machine, and the three
# ECDSA cells' glv.kernel_ms / glv.prepare_ms are no longer the ledger's
ECDSA_LOWERED = {
    "_glv_prepare_program":
        "76661e55a7c2d57f7cb5c38a35f6801b121e5470362511e9863e56ac5eec1cbf",
    "_glv_dev_program":
        "14c3d1693436da6970b89c2a86509e6ebeec30420e988fa2fcd22e253372c612",
}


def _digest(text: str) -> str:
    import hashlib

    return hashlib.sha256(text.encode()).hexdigest()


def test_glv_prepare_program_lowers_to_the_text_it_had_for_v5e(one_chip,
                                                                chip_forms):
    """The stage both bucket kinds share: a Schnorr bucket hands it u1 = s,
    u2 = n - e, Q = P, r and a zero wrap plane and changes nothing of it."""
    from bitcoincashplus_tpu.ops import secp256k1 as dev

    text = dev._glv_prepare_program.lower(
        *_verify_args(one_chip, 8192)).as_text()
    assert _digest(text) == ECDSA_LOWERED["_glv_prepare_program"]


def test_glv_schnorr_program_takes_its_tables_for_v5e(one_chip, chip_forms):
    """The Schnorr bucket's second stage has PR 39's form: the six stacked
    tables are arguments of its main and none is stacked inside it (the
    prepare stage's first ten outputs are its arguments). Its loops are
    the ladder, the comb and the fourteen runs of squarings of the Euler
    power: the squarings are not unrolled."""
    from bitcoincashplus_tpu.ops import secp256k1 as dev

    lanes = 8192
    text = dev._glv_schnorr_program.lower(
        *_ladder_args(one_chip, lanes)[:10]).as_text()
    table = f"tensor<16x{dev.N_LIMBS}x{lanes}xui32>"
    main = next(ln for ln in text.splitlines()
                if "func.func public @main" in ln)
    assert main.count(table) == 6
    assert not [ln for ln in text.splitlines()
                if "stablehlo.concatenate" in ln
                and ln.rstrip().endswith(table)]
    assert text.count("stablehlo.while") == 2 + 14


# the node's reindex buckets (node.py _import_block_files_native); compile
# seconds measured on this 8-core sandbox for PR 22: 215 / 228 / 242 s;
# 114 s at 8,192 since PR 33 halved the field normaliser's launches; since
# PR 39 two programs, 84 + 47 s at 8,192
@pytest.mark.slow(reason="AOT compile minutes per bucket (sandbox)")
@pytest.mark.parametrize("bucket", [1024, 2048, 8192])
def test_glv_verify_bucket_compiles_for_v5e(one_chip, chip_forms, bucket):
    """The DEFAULT verify kernel (decompose and tables, then the GLV
    ladder: two programs) and the Schnorr bucket's second stage fit one chip: temp stays far below the 16 GB
    of HBM at every bucket, and so do the tables the first hands the
    second."""
    from bitcoincashplus_tpu.ops import secp256k1 as dev

    prepare = dev._glv_prepare_program.lower(
        *_verify_args(one_chip, bucket)).compile()
    ladder = dev._glv_dev_program.lower(
        *_ladder_args(one_chip, bucket)).compile()
    schnorr = dev._glv_schnorr_program.lower(
        *_ladder_args(one_chip, bucket)[:10]).compile()
    for compiled in (prepare, ladder, schnorr):
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < 2 << 30, mem
    assert prepare.memory_analysis().output_size_in_bytes < 1 << 30
