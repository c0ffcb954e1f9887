"""Operation counts from shapes (the ``p2m_vgg`` kind's work per frame),
the roofline arithmetic and the table of peaks."""
import json
import os

import pytest

from bench import run
from bench.stats import least_seconds
from conftest import ROOT, load_config


def test_cifar10_backbone_macs(p2m_vgg):
    # P2M stride 2: 32 -> 16; pools 16 -> 8 -> 4 -> 2 -> 1
    # per layer hw*hw * 9*cin * cout:
    #   16x16: 256*9*32*64 + 256*9*64*64              =  4,718,592 +  9,437,184
    #    8x8:   64*9*64*128 + 64*9*128*128             =  4,718,592 +  9,437,184
    #    4x4:   16*9*128*256 + 2 * 16*9*256*256        =  4,718,592 + 18,874,368
    #    2x2:    4*9*256*512 + 2 * 4*9*512*512         =  4,718,592 + 18,874,368
    #    1x1:  3 * 1*9*512*512                         =  7,077,888
    assert p2m_vgg.backbone_macs(load_config("vgg16_cifar10")) == 82_575_360


def test_imagenet_backbone_macs(p2m_vgg):
    # P2M stride 2: 224 -> 112; pools 112 -> 56 -> 28 -> 14 -> 7
    #   112^2: 12544*9*32*64 + 12544*9*64*64           = 231,211,008 + 462,422,016
    #    56^2:  3136*9*64*128 + 3136*9*128*128         = 231,211,008 + 462,422,016
    #    28^2:   784*9*128*256 + 2 * 784*9*256*256     = 231,211,008 + 924,844,032
    #    14^2:   196*9*256*512 + 2 * 196*9*512*512     = 231,211,008 + 924,844,032
    #     7^2:  3 * 49*9*512*512                       = 346,816,512
    cfg = load_config("vgg16_imagenet")
    assert p2m_vgg.backbone_macs(cfg) == 4_046_192_640


def test_frontend_macs_and_bytes(p2m_vgg):
    cfg = load_config("vgg16_cifar10")
    # 16*16 output pixels * 3*3*3 taps * 32 channels
    assert p2m_vgg.frontend_macs(cfg) == 221_184
    # frame 32*32*3 f32 + activations 16*16*32 f32 + draw words 16*16*32 u16
    assert p2m_vgg.frontend_bytes_per_frame(cfg) == 12_288 + 32_768 + 16_384
    # 112*112*27*32 at 224x224
    assert p2m_vgg.frontend_macs(load_config("vgg16_imagenet")) == 10_838_016


def test_model_flops_and_frontend_bound(p2m_vgg):
    cfg = load_config("vgg16_cifar10")
    work = p2m_vgg.work(cfg)
    # 2 * (backbone + head 512*10 + frontend)
    assert work["model"]["flops"] == 2 * (82_575_360 + 5_120 + 221_184)
    assert work["backbone"]["flops"] == 2 * 82_575_360
    peak = run.peak_of(ROOT, "TPU v5 lite")
    least, bound = least_seconds(work["p2m_frontend"], 1000, peak)
    assert bound == "memory"
    assert least == pytest.approx(1000 * 61_440 / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        run.peak_of(ROOT, "TPU v99")


def test_peaks_name_their_source():
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        table = json.load(f)
    assert "Google Cloud" in table["source"]
    assert table["devices"]["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
