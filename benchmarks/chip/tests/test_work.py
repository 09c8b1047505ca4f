"""The yardstick's counts, worked by hand at qwen3-14b shapes.

Run by path: ``python -m pytest benchmarks/chip/tests``."""
import pytest

from benchmarks.chip import spec, work

PEAKS = work.peaks("TPU v5 lite")


@pytest.fixture(scope="module")
def lutmu():
    return spec.load("qwen3-14b-lutmu")


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def test_lutmu_gate_call_at_decode_batch(lutmu):
    # B=32 rows, C=5120/8=640 codebooks, G=16 leaves, N=4*2176=8704
    # pruned package columns, depth 4
    ops, nbytes = work.lutmu_layer_sites(lutmu, 32)[0]
    assert ops == 32 * 640 * 15 + 32 * 640 * 8704 == 178_565_120
    # table 640*16*8704 int8; f32 split values 32*640*4, thresholds
    # 640*15, scale+offset 2*8704, output 32*8704
    assert nbytes == 89_128_960 + 4 * (81_920 + 9_600 + 17_408 + 278_528)
    assert nbytes == 90_678_784
    # bound by the table bytes: 90.68 MB at 819 GB/s
    assert work.roofline_s(ops, nbytes, PEAKS) == pytest.approx(
        90_678_784 / 819e9)


def test_lutmu_down_call_at_prefill_chunk(lutmu):
    # B=256, C=17408/8=2176, N=5120
    ops, nbytes = work.lutmu_layer_sites(lutmu, 256)[2]
    assert ops == 256 * 2176 * 15 + 256 * 2176 * 5120 == 2_860_482_560
    assert nbytes == 2176 * 16 * 5120 + 4 * (
        256 * 2176 * 4 + 2176 * 15 + 2 * 5120 + 256 * 5120)
    assert nbytes == 178_257_920 + 4 * 3_581_824 == 192_585_216
    assert work.roofline_s(ops, nbytes, PEAKS) == pytest.approx(
        192_585_216 / 819e9)


def test_token_flops_count_lutmu_as_dense(lutmu):
    dense = spec.load("qwen3-14b-dense")
    # per layer: q,k,v,o = 5120*(40+16)*128 + 40*128*5120 and three
    # 5120x17408 MLP products, times 2; attention 4*40*128 per position
    proj = 36_700_160 + 26_214_400 + 267_386_880
    per_layer = 2 * proj + 4 * 40 * 128 * 1000
    head = 2 * 5120 * 151936
    assert 16 * per_layer + head == 12_453_150_720
    assert work.token_flops(lutmu, 1000, head=True) == 12_453_150_720
    assert work.token_flops(dense, 1000, head=False) == 12 * per_layer


def test_prefill_chunk_flops_match_the_issue_estimate(lutmu):
    # a 256-token chunk at the start of a prompt: about 2.7 TFLOP
    total = sum(work.token_flops(lutmu, i + 1, head=(i == 255))
                for i in range(256))
    assert 2.6e12 < total < 2.8e12
