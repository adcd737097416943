"""The operation and byte counts behind ``decode_mfu`` and
``paged_decode_roofline``, against counts made by hand."""
import json

import dense_decoder as fam
import opcount
from benchkit import HERE


def _dims(name):
    model = json.loads((HERE / "configs" / f"{name}.json").read_text())
    assert opcount.family(model) is fam
    return fam.dims_of(model)


def test_stablelm_decode_counts():
    d = _dims("stablelm-1.6b")
    # per layer: q 2048x2048, k and v 2048x2048 each, o 2048x2048, MLP
    # 3 x 2048x5632 = 51,380,224; 24 layers plus the 2048x100352 head
    assert fam.matmul_params(d) == 24 * 51_380_224 + 205_520_896
    # 2 x 1,438,646,272 matmul FLOPs, and attention over 101 keys:
    # 24 layers x 4 x 32 heads x 64 x 101
    assert fam.decode_flops(d, 100) == 2_877_292_544 + 19_857_408
    # 101 keys fill 7 pages of 16: 7 x 16 x 32 x 64 x (K, V) x 2 bytes,
    # plus the query in and the output out, 2 x 32 x 64 x 2 bytes
    assert fam.attn_bytes(d, 100, 16) == 917_504 + 8_192
    assert fam.attn_flops(d, 100) == 827_392


def test_nemo_decode_counts():
    d = _dims("mistral-nemo-12b")
    # per layer: q 5120x4096, k and v 5120x1024, o 4096x5120, MLP
    # 3 x 5120x14336 = 272,629,760; 10 layers plus the 5120x131072 head
    assert fam.matmul_params(d) == 10 * 272_629_760 + 671_088_640
    assert fam.decode_flops(d, 0) == 6_794_772_480 + 163_840
    # one key: one page of 16 tokens x 8 KV heads x 128 x (K, V) x 2 bytes
    assert fam.attn_bytes(d, 0, 16) == 65_536 + 16_384


def test_decoded_positions_follow_the_requests():
    reqs = [{"prompt": 10, "out": 5, "first": 0.0, "finished": 4.0},
            {"prompt": 3, "out": 1, "first": 1.0, "finished": 1.0},
            {"prompt": 7, "out": 3, "first": None, "finished": None}]
    # tokens 2..5 of the first request leave at 1, 2, 3, 4 s, decoded at
    # positions 10..13; the window [1.5, 3.5) holds two of them
    assert opcount.decoded_positions(reqs, 0.0, 10.0) == [10, 11, 12, 13]
    assert opcount.decoded_positions(reqs, 1.5, 3.5) == [11, 12]
