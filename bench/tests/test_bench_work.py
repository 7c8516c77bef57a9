"""work.py's counts against numbers worked by hand at the bring-up
shapes (64x64x3 images, hidden 128, latent 64, K 256, 32 images)."""
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2])]
from bench.harness import work  # noqa: E402

VQ = {"in_channels": 3, "hidden": 128, "n_res_blocks": 2, "latent_dim": 64,
      "codebook_size": 256, "n_groups": 1, "n_slices": 1}
GSVQ = dict(VQ, n_groups=8, n_slices=4)


def test_encoder_and_decoder_layers():
    # down1 32*32 out x 3 x 64 x 4*4 x 2, down2 16*16 x 64 x 128 x 16 x 2,
    # mid 256 x 128 x 128 x 9 x 2, res (c1 3x3 + c2 1x1) x 2, to_latent
    assert work.encoder_layers(VQ, 64) == [
        6_291_456, 67_108_864, 75_497_472,
        75_497_472, 8_388_608, 75_497_472, 8_388_608, 4_194_304]
    assert sum(work.encoder_layers(VQ, 64)) == 320_864_256
    assert sum(work.decoder_layers(VQ, 64)) == 278_921_216


def test_client_ops_bring_up_shapes():
    # fine-tune: fwd 608,174,080 (convs + 2*256*256*64 match) + bwd
    # 2 * 599,785,472 - 6,291,456; encoder pass 320,864,256; encode
    # 8,388,608 + 256*64 sums. Per image 2,130,722,816; 32 images.
    assert work.client_ops(VQ, 64, 32) == 32 * 2_130_722_816


def test_packed_bytes_and_bits():
    assert work.code_bits(VQ) == 8 and work.code_bits(GSVQ) == 3
    # 32 images x 256 codes at 8 bits: 8,192 B (256 B per image)
    assert work.packed_bytes(VQ, 32 * 256) == 8192
    # GSVQ: 32 x 256 x 4 codes at 3 bits, groups of 32 codes in 12 B
    assert work.packed_bytes(GSVQ, 32 * 256 * 4) == 12288
    assert work.packed_bytes(VQ, 5) == 8             # padded to 4 codes


def test_encode_and_decode_work():
    n = 32 * 256
    assert work.encode_ops(VQ, n) == 2 * n * 256 * 64 + n * 64
    assert work.encode_bytes(VQ, n) == (4 * n * 64 + 4 * 256 * 64 + 8192
                                        + 4 * 256 + 4 * 256 * 64)
    assert work.encode_ops(GSVQ, n) == (2 * n * 256 * 64 + 2 * n * 4 * 256
                                        + n * 4 * 64)
    assert work.decode_bytes(VQ, n) == 8192 + 4 * 256 * 64 + 4 * n * 64
    assert work.decode_bytes(GSVQ, n) == 12288 + 4 * 8 * 64 + 4 * n * 64


def test_roofline_bound():
    peak = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    t, bound = work.roofline_seconds(197e12, 1.0, peak)
    assert bound == "compute" and t == 1.0
    t, bound = work.roofline_seconds(0, 819e9, peak)
    assert bound == "memory" and t == 1.0
