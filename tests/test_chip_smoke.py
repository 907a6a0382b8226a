"""chip_smoke.py's phases at toy sizes on the CPU mesh, its refusal to pass
without a TPU, and the compile-cache placement it relies on.

The script's own path has no CPU branch: these tests call the phase
functions with tiny sizes and ``backend="cpu"``, and assert what only the
CPU can promise (token identity with ``generate()``); the chip-only
assertions (Mosaic kernels in the compiled step, allocator statistics) live
in ``chip_smoke.main`` and are exercised on the chip.
"""

import os
import subprocess
import sys

import jax
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402
import tpu_dist.dist as dist  # noqa: E402
from tpu_dist.utils.compile_cache import ensure_compile_cache  # noqa: E402

TINY_LM = dict(vocab_size=512, dim=64, depth=2, num_heads=2, max_seq_len=128)
TINY_RUN = dict(model_kw=TINY_LM, per_chip_batch=2, seq_len=128, lr=0.3)


@pytest.fixture(autouse=True)
def _clean_group():
    if dist.is_initialized():
        dist.destroy_process_group()
    yield
    if dist.is_initialized():
        dist.destroy_process_group()


# -- compile cache placement --------------------------------------------------

@pytest.fixture
def _restore_cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_dir_from_environment_is_left_to_jax(monkeypatch, tmp_path,
                                                   _restore_cache_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert ensure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None   # config untouched


def test_cache_dir_defaults_to_one_fixed_path_in_the_checkout(
        monkeypatch, _restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = ensure_compile_cache()
    assert first == os.path.join(_REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert ensure_compile_cache() == first                # twice: same path
    with open(os.path.join(_REPO, ".gitignore")) as f:
        assert "/.jax_cache/" in f.read().split()


# -- the script refuses to pass off the chip ----------------------------------

def test_script_exits_nonzero_naming_the_platform_without_a_tpu():
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=_REPO,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "platform: cpu" in proc.stdout
    assert "JAX resolved platform 'cpu'" in proc.stdout
    assert '"ok"' not in proc.stdout                      # no result line


def test_mosaic_kernel_names_are_read_from_custom_call_lines():
    hlo = ('%a = bf16[8] custom-call(%x), custom_call_target='
           '"tpu_custom_call", metadata={op_name="jit(f)/flash_fwd"}\n'
           '%b = f32[8] fusion(%y), metadata={op_name="fused_ce_fwd"}\n')
    assert chip_smoke.mosaic_kernels(
        hlo, chip_smoke.TRAINER_KERNELS) == {"flash_fwd"}


# -- the phases, tiny ---------------------------------------------------------

def test_trainer_and_server_phases_tiny():
    tr = chip_smoke.phase_trainer("cpu", steps=5, **TINY_RUN)
    assert len(tr["losses"]) == 5 and tr["losses"][-1] < tr["losses"][0]
    assert tr["kernels"] == set()        # interpret mode: no Mosaic calls
    assert len(tr["peak_bytes"]) == 8    # one entry per device of the group
    assert not dist.is_initialized()     # the phase cleans up after itself

    requests = [(5, 8), (60, 12), (17, 8), (5, 8), (60, 12)]
    sv = chip_smoke.phase_server(TINY_LM, slots=2, requests=requests)
    assert sv["identical"] == len(requests)   # the CPU contract
    assert sv["stats"]["completed"] == len(requests)


def test_phase_fails_loudly():
    with pytest.raises(dist.BackendMismatchError):
        chip_smoke.phase_convnet("tpu", per_chip_batch=2, steps=2)
    with pytest.raises(ValueError, match="more requests than slots"):
        chip_smoke.phase_server(TINY_LM, slots=4, requests=[(5, 4)])


@pytest.mark.parametrize("heads", [(1, 2), (2, 4)], ids=["rep2", "two-pairs"])
def test_the_scan_kernels_check_tiny(heads):
    """``check_delta_scan`` as the chip runs it (a padded tail, a drawn
    state, the state donated), interpreted here at 200 positions."""
    chip_smoke.check_delta_scan(seq=200, key_heads=heads[0],
                                value_heads=heads[1], k_dim=128, v_dim=128)


@pytest.mark.parametrize("group", [1, 5], ids=["by-head", "grouped"])
def test_the_slot_decode_kernels_check_tiny(group):
    """``check_decode_attention`` as the chip runs it, both forms
    (ISSUE 45), interpreted here at twelve slots of 512."""
    chip_smoke.check_decode_attention(slots=12, heads=2, head_dim=64,
                                      max_len=512, group=group)


def test_the_expert_combine_checks_tiny():
    """``check_moe_combine`` as the chip runs it (ISSUE 46), interpreted
    here at 200 tokens of which the last 20 are alike."""
    chip_smoke.check_moe_combine(tokens=200, top_k=4, rows=256, dim=128,
                                 share=1 / 8)


@pytest.mark.slow
def test_kernel_convnet_and_multichip_phases_tiny():
    chip_smoke.phase_kernels(
        flash=dict(batch=2, seq=256, heads=2, head_dim=64),
        ce=dict(rows=64, vocab=1000),
        moe=dict(tokens=256, dim=128, experts=4, top_k=2),
        decode=dict(slots=12, heads=2, head_dim=64, max_len=512),
        grouped=dict(slots=12, heads=2, head_dim=64, max_len=512, group=5),
        latent=dict(slots=12, heads=4, latent=48, values=32, max_len=512),
        state=dict(slots=3, heads=2, k_dim=64, v_dim=128),
        scan=dict(seq=150, key_heads=1, value_heads=2, k_dim=128, v_dim=128),
        combine=dict(tokens=200, top_k=4, rows=256, dim=128, share=1 / 8))
    chip_smoke.phase_convnet("cpu", per_chip_batch=8, steps=12)
    tr = chip_smoke.phase_trainer("cpu", steps=2, **TINY_RUN)
    chip_smoke.phase_multichip("cpu", dp_first_loss=tr["losses"][0],
                               **TINY_RUN)
