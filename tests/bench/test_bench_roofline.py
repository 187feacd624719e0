"""Operations and bytes of each kernel and step, worked by hand at the
cells' shapes (Qwen3-1.7B: 28 layers, d 2048, 16 q / 8 kv heads of 128,
d_ff 6144, vocab 151,936; the ring cell's S = 32,768)."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import roofline  # noqa: E402
from bench.peaks import PEAKS, peaks_for  # noqa: E402

S, HQ, HKV, D, L = 32768, 16, 8, 128, 28


@pytest.fixture
def dims():
    c = json.loads((ROOT / "bench/configs/qwen3-1.7b-serve.json").read_text())
    return roofline.Dims.from_config(c)


def test_layer_matmul_params(dims):
    # q 2048x2048, k and v 2048x1024, o 2048x2048, gate/up/down 2048x6144
    assert dims.layer_matmul_params == 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 6144
    assert dims.layer_matmul_params * L == 1_409_286_144


def test_flash_forward_causal():
    w = roofline.flash_fwd(S, HQ, HKV, D)
    assert w.flops == 2 * 2 * (S * S / 2) * HQ * D == 4_398_046_511_104
    assert w.bytes == S * 32 * D * 2 + S * HQ * D * 2 + S * HQ * 4
    assert w.bound(peaks_for("TPU v5 lite")) == "compute"
    assert w.min_seconds(PEAKS["TPU v5 lite"]) == pytest.approx(4_398_046_511_104 / 197e12)


def test_flash_backward_five_matmuls():
    w = roofline.flash_bwd(S, HQ, HKV, D)
    assert w.flops == 5 * 2 * (S * S / 2) * HQ * D
    assert w.bytes == 2 * S * 32 * D * 2 + 2 * S * HQ * D * 2 + S * HQ * 4


def test_training_flops_no_recompute():
    assert roofline.attention_train_flops(S, HQ, D) == 6 * S * S * HQ * D
    # forward + backward at the cell's shape over four chips' peak: 16.7 ms
    assert roofline.attention_train_flops(S, HQ, D) / (4 * 197e12) == pytest.approx(0.016746, rel=1e-3)


def test_paged_decode_bytes(dims):
    w = roofline.paged_decode(dims, [4096])
    per_layer = 4096 * HKV * D * 2 * 2 + 4096 * 4 + HQ * D * 2 * 2
    assert w.bytes == L * per_layer == 470_450_176
    assert w.flops == 4 * 4096 * HQ * D * L
    assert w.bound(PEAKS["TPU v5 lite"]) == "memory"


def test_prefill_attention_causal_chunk(dims):
    # a first chunk of 256 tokens: query p attends p + 1 keys
    w = roofline.prefill_attention(dims, [(0, 256)])
    assert w.flops == 4 * (256 * 257 // 2) * HQ * D * L
    assert w.bytes == L * (256 * HKV * D * 2 * 2 + 256 * HQ * D * 2 * 2)
    # the second chunk also reads the first chunk's K/V
    w2 = roofline.prefill_attention(dims, [(256, 256)])
    assert w2.flops == 4 * (256 * 256 + 256 * 257 // 2) * HQ * D * L
    assert w2.bytes == L * (512 * HKV * D * 2 * 2 + 256 * HQ * D * 2 * 2)


def test_serve_model_flops_counts_valid_tokens_only(dims):
    mm = 2 * 1_409_286_144
    head = 2 * 2048 * 151936
    att = 4 * HQ * D * L
    assert roofline.serve_model_flops(dims, [], [1000]) == mm + head + att * 1000
    assert roofline.serve_model_flops(dims, [(0, 3)], []) == 3 * mm + att * (1 + 2 + 3)
    assert roofline.serve_model_flops(dims, [], []) == 0


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")
    assert PEAKS["TPU v5 lite"]["source"] == "Google Cloud, TPU v5e"
