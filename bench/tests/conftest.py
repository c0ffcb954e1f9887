"""Makes the checkout root (for ``bench``) and ``src`` (for the program)
importable, and gives the tests a tiny copy of each configuration."""
import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# the smallest VGG the program knows, at frames small enough for the
# Pallas interpreter; the CPU computes f32 convs on f32 operands
TINY = {"arch": "vgg_tiny", "plan": [32, "M", 64, "M", 64, "M"],
        "microbatch": 4,
        "precision": {"frontend": "float32", "backbone_operands": "float32",
                      "accumulation": "float32"}}


def load_config(name: str) -> dict:
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def tiny(name: str, hw: int = 8) -> dict:
    cfg = copy.deepcopy(load_config(name))
    cfg.update(copy.deepcopy(TINY))
    cfg["in_hw"] = hw
    return cfg


@pytest.fixture
def tiny_cifar():
    return tiny("vgg16_cifar10")


@pytest.fixture
def tiny_imagenet():
    return tiny("vgg16_imagenet", hw=16)
