import pytest

from perfbench.harness import counts, spec


def _cfg(name):
    return spec.load_json(f"{spec.BENCH_DIR}/configs/{name}.json")


def _flops(name):
    cfg = _cfg(name)
    return spec.module(cfg["reference"]["model"]).train_flops_per_example(cfg)


def test_dlrm_flops_at_published_widths():
    # bottom MLP 13-512-256-128, top MLP 479-1024-1024-512-256-1,
    # interaction z (27 x 128) @ z.T
    bot = 13 * 512 + 512 * 256 + 256 * 128                    # 170,496 MACs
    top = 479 * 1024 + 1024 * 1024 + 1024 * 512 + 512 * 256 + 256 * 1  # 2,194,688
    inter = 27 * 27 * 128                                     # 93,312
    fwd = bot + top + inter
    bwd = (bot + top) + (bot - 13 * 512 + top) + 2 * inter
    assert 2 * (fwd + bwd) == 14_737_664  # 14.7 MFLOP per example
    for name in ("dlrm-mlperf-rows1of8", "dlrm-mlperf-rows1of2"):
        assert _flops(name) == 2 * (fwd + bwd)


def test_dcnv2_flops_at_published_widths():
    d0 = 13 + 26 * 16                                          # 429
    cross = 3 * d0 * d0                                        # 552,123 MACs
    deep = 429 * 1024 + 1024 * 1024 + 1024 * 512 + 512 * 1     # 2,012,672
    assert _flops("dcn-v2") == 6 * (cross + deep)
    assert 6 * (cross + deep) == 15_388_770


def test_embed_update_bytes_and_peaks():
    cfg = _cfg("dlrm-mlperf-rows1of8")
    # per unique row: 128 fp32 + one fp32 accumulator, read and written
    assert counts.embed_update_bytes(cfg, 1000) == 1000 * 2 * (128 * 4 + 4)
    assert counts.peak("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError):
        counts.peak("cpu")
