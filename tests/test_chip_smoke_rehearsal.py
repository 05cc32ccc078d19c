"""CPU rehearsal of ``chip_smoke.py``: every phase function at tiny size
in this process, with the Pallas kernels in interpret mode and the
platform predicate patched so dispatch takes the TPU branches.  What only
the chip can show (``tpu_custom_call`` in the compiled step, memory
stats, the device check itself) is asserted by ``chip_smoke.main`` there,
not here."""

import importlib

import pytest

import chip_smoke
from deepspeed_tpu.models import get_model_config

_KERNEL_MODULES = ("deepspeed_tpu.ops.pallas.flash_mha",
                   "deepspeed_tpu.ops.pallas.paged_attention",
                   "deepspeed_tpu.ops.pallas.ssd_ragged",
                   "deepspeed_tpu.ops.pallas.latent_index")
_DISPATCH_MODULES = ("deepspeed_tpu.ops.flash_attention",
                     "deepspeed_tpu.inference.v2.model")


@pytest.fixture
def tpu_branches(monkeypatch):
    for name in _KERNEL_MODULES:
        monkeypatch.setattr(importlib.import_module(name), "INTERPRET", True)
    for name in _DISPATCH_MODULES:
        monkeypatch.setattr(importlib.import_module(name), "on_tpu",
                            lambda: True)


def _tiny_serve_model():
    # head_dim 128: the narrowest head the paged kernel takes
    return get_model_config("mistral-tiny", hidden_size=256, num_heads=2,
                            num_kv_heads=1)


_TINY_ENGINE = {"dtype": "float32", "max_context": 64,
                "memory_config": {"num_blocks": 32, "block_size": 8}}


def test_device_phase_refuses_cpu():
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU"):
        chip_smoke.device_phase(1)


def test_main_prints_no_result_without_tpu(capsys):
    assert chip_smoke.main([]) == 1
    assert '"ok"' not in capsys.readouterr().out


def test_ssd_phase_tiny(tpu_branches):
    out = chip_smoke.ssd_phase(heads=4, head_dim=32, state=16, groups=2,
                               slots=6, chunk_rows=21, chunk=16)
    assert out["silent"] < 1e-6 and out["y"] < 0.02


def test_append_phase_tiny(tpu_branches):
    out = chip_smoke.append_phase(layers=3, kv_heads=2, head_dim=128,
                                  block_size=8, pages=40, chunk_rows=21)
    assert out["same"] and out["rows"] == 36


def test_sink_phase_tiny(tpu_branches):
    out = chip_smoke.sink_phase(heads=16, head_dim=192, value_dim=128,
                                window=12, block_size=8, blocks=8, pages=24,
                                chunk_rows=21, decode_rows=(13,))
    assert out["window"] < 0.03 and out["full"] < 0.03
    # and the step of one-row runs alone in their bucket
    assert max(out["window 13 rows"], out["full 13 rows"]) < 0.03
    assert min(out["window sign"], out["window heads"]) > 10 * out["window"]


def test_index_phase_tiny(tpu_branches):
    out = chip_smoke.index_phase(heads=4, dim=16, block_size=8, blocks=8,
                                 chunk_rows=21)
    assert out["scores"] < 1e-5 and out["overlap"] == 1.0


def test_train_phase_tiny(tpu_branches):
    out = chip_smoke.train_phase(get_model_config("gpt2-tiny"), micro_batch=1,
                                 gas=2, seq=128, steps=3, mesh={"data": 1})
    assert out["losses"][-1] < out["losses"][0]
    assert out["param_devices"] == [0]


def test_serve_phase_tiny(tpu_branches):
    out = chip_smoke.serve_phase(_tiny_serve_model(), _TINY_ENGINE,
                                 n_requests=3, prompt_len=12, new_tokens=6)
    assert out["attention"] == "paged_pallas"
    assert all(len(t) == 6 for t in out["tokens"])
    assert out["ties"] == 0        # fp32 here: exact


def test_sharded_train_phase_tiny(tpu_branches):
    """Also the one CPU test of the flash kernel under a mesh: the
    one-device losses and the 2x2 losses both come through the kernel
    (interpreted), the latter inside ``flash_attention``'s shard_map."""
    out = chip_smoke.sharded_train_phase(get_model_config("gpt2-tiny"),
                                         seq=128, steps=3)
    assert len(out["one"]["param_devices"]) == 1
    assert len(out["four"]["param_devices"]) == 4
    assert "all-gather" in out["four"]["hlo"]


def test_replicas_phase_tiny():
    out = chip_smoke.replicas_phase(_tiny_serve_model(), _TINY_ENGINE,
                                    n_replicas=4, n_requests=8,
                                    prompt_len=12, new_tokens=6)
    assert [len(h) for h in out["homes"]] == [1, 1, 1, 1]
    assert out["ties"] == 0


def test_compare_streams_takes_near_ties_only():
    """A stream may leave its reference at a near-tie of the engine's own
    logits and nowhere else."""
    import numpy as np

    class Eng:                      # logits: token 3 on top, 5 a near-tie
        def put(self, uids, toks):
            logits = np.zeros(8, np.float32)
            logits[3], logits[5], logits[6] = 4.0, 3.99, 2.0
            return {uids[0]: logits}

        def flush(self, uid):
            pass

    want = [[1, 2, 3, 4]]
    assert chip_smoke._compare_streams(Eng(), [[0]], [[1, 2, 5, 7]], want,
                                       "t") == 1
    assert chip_smoke._compare_streams(Eng(), [[0]], want, want, "t") == 0
    with pytest.raises(chip_smoke.SmokeFailure, match="no near-tie"):
        chip_smoke._compare_streams(Eng(), [[0]], [[1, 2, 6, 4]], want, "t")
    with pytest.raises(chip_smoke.SmokeFailure, match="returned 3 of 4"):
        chip_smoke._compare_streams(Eng(), [[0]], [[1, 2, 3]], want, "t")


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    """``$JAX_COMPILATION_CACHE_DIR`` set: nothing is set in code.  Unset:
    the fixed ``<checkout>/.jax_cache``, handed on to child processes."""
    import os

    from deepspeed_tpu.utils import platform

    updates = []
    monkeypatch.setattr(platform.jax.config, "update",
                        lambda key, value: updates.append((key, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert platform.setup_compile_cache() == str(tmp_path)
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    checkout = os.path.dirname(os.path.abspath(chip_smoke.__file__))
    path = platform.setup_compile_cache()
    assert path == os.path.join(checkout, ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", path)]
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == path
