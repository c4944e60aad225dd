"""Operations and bytes of the ResNet forwards, from shapes.

A network is listed as its convolutions and dense layers, each a
``Layer``; the forward's operations are those of the matrix units:
2 x multiply-adds of every convolution and dense layer.  A tap of a
convolution that falls on SAME padding reads a zero and is not counted,
as XLA's cost analysis does not count it either.  GroupNorm, ReLU,
the residual adds and the pooling are left out: they are a few percent of
the total and not what a model FLOP count measures (``tests/bench``
checks the total against XLA's own count).
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Layer:
    name: str
    hw: int          # input height = width
    k: int           # kernel height = width (1 for a dense layer)
    stride: int
    cin: int
    cout: int
    dense: bool = False

    @property
    def out_hw(self) -> int:
        return 1 if self.dense else -(-self.hw // self.stride)   # SAME

    def taps(self) -> int:
        """Kernel taps, summed over the output positions of one axis,
        that fall inside the input (SAME padding, low side first)."""
        pad = max((self.out_hw - 1) * self.stride + self.k - self.hw, 0)
        lo = pad // 2
        return sum(1 for o in range(self.out_hw) for i in range(self.k)
                   if 0 <= o * self.stride + i - lo < self.hw)

    def flops(self, n: int = 1) -> float:
        """Operations for ``n`` examples."""
        return 2.0 * n * self.taps() ** 2 * self.cin * self.cout

    def bytes(self, n: int = 1, clients: int = 1, itemsize: int = 4) -> float:
        """Input, weights and output of one call over ``clients`` stacked
        clients of ``n`` examples each: each read or written once."""
        x = n * self.hw ** 2 * self.cin
        y = n * self.out_hw ** 2 * self.cout
        w = self.k ** 2 * self.cin * self.cout
        return float(itemsize * clients * (x + y + w))


def resnet8(cfg: dict) -> list[Layer]:
    """3 stages of one basic block (width w, 2w, 4w); the stem at stride 1."""
    hw, w = cfg["image_hw"], cfg["width"]
    out = [Layer("stem", hw, 3, 1, cfg["channels"], w)]
    cin = w
    for i, (cout, stride) in enumerate(((w, 1), (2 * w, 2), (4 * w, 2))):
        out.append(Layer(f"block{i + 1}.conv1", hw, 3, stride, cin, cout))
        hw_out = -(-hw // stride)
        out.append(Layer(f"block{i + 1}.conv2", hw_out, 3, 1, cout, cout))
        if cin != cout:
            out.append(Layer(f"block{i + 1}.proj", hw, 1, stride, cin, cout))
        hw, cin = hw_out, cout
    out.append(Layer("fc", 1, 1, 1, cin, cfg["num_classes"], dense=True))
    return out


def resnet50(cfg: dict) -> list[Layer]:
    """He et al. 2016, Table 1: a 7x7 stride-2 stem, a 3x3 stride-2 max
    pool, bottleneck stages [3, 4, 6, 3] of widths 64..512 (x4 out)."""
    hw = -(-cfg["image_hw"] // 2)
    out = [Layer("stem", cfg["image_hw"], 7, 2, cfg["channels"], 64)]
    hw = -(-hw // 2)                                   # max pool
    cin = 64
    for si, (cmid, blocks) in enumerate(((64, 3), (128, 4), (256, 6),
                                         (512, 3))):
        for bi in range(blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            name = f"s{si}b{bi}"
            out.append(Layer(f"{name}.conv1", hw, 1, 1, cin, cmid))
            out.append(Layer(f"{name}.conv2", hw, 3, stride, cmid, cmid))
            hw_out = -(-hw // stride)
            out.append(Layer(f"{name}.conv3", hw_out, 1, 1, cmid, 4 * cmid))
            if cin != 4 * cmid:
                out.append(Layer(f"{name}.proj", hw, 1, stride, cin,
                                 4 * cmid))
            hw, cin = hw_out, 4 * cmid
    out.append(Layer("fc", 1, 1, 1, cin, cfg["num_classes"], dense=True))
    return out


ARCHS = {"resnet8": resnet8, "resnet50": resnet50}


def layers(cfg: dict) -> list[Layer]:
    return ARCHS[cfg["model"]](cfg)


def forward_flops(cfg: dict) -> float:
    """Operations of one example's forward."""
    return sum(layer.flops() for layer in layers(cfg))


def convs(cfg: dict) -> list[Layer]:
    return [layer for layer in layers(cfg) if not layer.dense]
