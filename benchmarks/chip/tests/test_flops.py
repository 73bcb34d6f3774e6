"""FLOP and byte counts against hand arithmetic, and the peak table."""
from pathlib import Path

import pytest

import peaks
import run
from traffic import ExampleSize

HERE = Path(__file__).resolve().parents[1]
flops = run.load_module(HERE / "flops" / "mllm.py")
flash = run.load_module(HERE / "metrics" / "flash_roofline.py")

TINY = {
    "d_model": 8, "n_heads": 2, "n_kv_heads": 1, "d_ff": 16, "n_layers": 1,
    "vocab_size": 10,
    "encoders": [{"name": "vision", "d_model": 4, "n_heads": 2, "d_ff": 8,
                  "n_layers": 1, "embed_dim": 3, "downsample": 1},
                 {"name": "audio", "d_model": 4, "n_heads": 2, "d_ff": 8,
                  "n_layers": 1, "embed_dim": 3, "downsample": 2}],
}


def test_text_example_by_hand():
    ex = ExampleSize("text", 3, 0, 0, ("text",))
    # LLM, per token: q 8x8, k 8x4, v 8x4, o 8x8, MLP 3 x 8x16 -> 576 MACs.
    dense = 1 * 3 * 2 * 576 + 2 * 2 * 8 * 10  # + LM head on the 2 labelled rows
    # Causal pairs 3*4/2 = 6; per pair and head 2*2*hd FLOPs, hd 4, 2 heads.
    attn = 6 * 2 * 2 * 4 * 2
    assert flops.train_flops([ex], TINY) == 3 * dense + 3.5 * attn


def test_audio_example_by_hand():
    ex = ExampleSize("asr", 2, 0, 3, ("audio", "text"))
    # Audio encoder on 3 tokens: 4*4*4 + 2*4*8 = 128 MACs per token;
    # connector on ceil(3/2) = 2 rows: 8x8 + 8x8 = 128 MACs per row;
    # bidirectional pairs 9, hd 2, 2 heads; input projection 3x4.
    enc_dense = 2 * 3 * 128 + 2 * 2 * 128
    enc_attn = 9 * 2 * 2 * 2 * 2
    proj = 2 * 3 * 3 * 4
    llm_tokens = 2 + 2
    llm_dense = 2 * llm_tokens * 576 + 2 * 2 * 8 * 10
    llm_attn = 10 * 2 * 2 * 4 * 2
    want = 3 * (enc_dense + llm_dense) + 3.5 * (enc_attn + llm_attn) + 2 * proj
    assert flops.llm_tokens(ex, TINY) == 4
    assert flops.supervised(ex) == 2
    assert flops.train_flops([ex], TINY) == want


def test_flash_work_does_not_depend_on_tiles_or_packing():
    exs = [ExampleSize("vqa", 40, 576, 0, ("vision", "text")),
           ExampleSize("asr", 30, 0, 101, ("audio", "text")),
           ExampleSize("text", 700, 0, 0, ("text",))]
    a = flash.work(exs, flops, TINY)
    b = flash.work(list(reversed(exs)), flops, TINY)
    c = tuple(map(sum, zip(*(flash.work([e], flops, TINY) for e in exs))))
    assert a == pytest.approx(b) and a == pytest.approx(c)
    # One causal site: 616 tokens, pairs 616*617/2, 2 heads, hd 4.
    f, _ = flash.work([ExampleSize("text", 616, 0, 0, ("text",))], flops, TINY)
    assert f == 3.5 * 2 * 2 * 2 * 4 * (616 * 617 // 2)


def test_flash_work_is_the_attention_share_of_the_step():
    """The kernels' FLOPs are the attention term of the step's model FLOPs."""
    ex = ExampleSize("vqa", 40, 576, 0, ("vision", "text"))
    f, _ = flash.work([ex], flops, TINY)
    attn = sum(3.5 * layers * flops.attention_fwd_flops(n, h, hd, causal)
               for n, h, _, hd, causal, layers in flops.attention_sites(ex, TINY))
    assert f == pytest.approx(attn) and f < flops.train_flops([ex], TINY)


def test_roofline_names_its_bound():
    p = peaks.peak_for("TPU v5 lite")
    assert p.least_seconds(197e12, 1.0) == (1.0, "compute")
    assert p.least_seconds(1.0, 819e9) == (1.0, "memory")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peak_for("TPU v9 imaginary")
    assert peaks.peak_for("TPU v5 lite").flops == 197e12


def test_flash_reader_takes_only_its_own_kernels():
    ex = ExampleSize("text", 616, 0, 0, ("text",))
    rec = {"kind": "train", "chips": 1, "model": TINY, "counter": flops,
           "peak": peaks.peak_for("TPU v5 lite"), "steps": [{"examples": [ex]}],
           "trace": {"kernels": {"gmm_op.1": 5.0}}}
    assert flash.read(rec) is None
    rec["trace"]["kernels"]["jvp_jit_flash_attention_op__.2"] = 1e-6
    f, b = flash.work([ex], flops, TINY)
    least, _ = rec["peak"].least_seconds(f, b)
    assert flash.read(rec) == pytest.approx(100 * least / 1e-6)
