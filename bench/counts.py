"""Work of a configuration's ops, from its shapes alone.

`ops(cfg)` walks a configuration file (bench/configs/<name>.json) into the
conv and dense ops one image runs, at the real geometry: every conv the
forward executes, ResNet's projection shortcuts and stride-2 convs
included. The plain reference builds its weights from the same list, so a
shape that disagrees with the program fails at set-up.

Counts are per op and per call of `k` real rows:

  flops(op, k)  2 * multiply-accumulates * k
  bytes(op, k)  float32 inputs, weights, bias and outputs, each once

`least_seconds` is the op's roofline floor on a chip: the larger of its
FLOPs over the peak rate and its bytes over the HBM bandwidth.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

WORD = 4      # float32


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    kind: str           # "conv" | "dense"
    h_in: int
    w_in: int
    c_in: int
    c_out: int
    k: int = 1
    stride: int = 1
    pad: int = 0
    groups: int = 1

    @property
    def h_out(self) -> int:
        return (self.h_in + 2 * self.pad - self.k) // self.stride + 1

    @property
    def w_out(self) -> int:
        return (self.w_in + 2 * self.pad - self.k) // self.stride + 1

    @property
    def w_shape(self):
        if self.kind == "dense":
            return (self.c_in, self.c_out)
        return (self.k, self.k, self.c_in // self.groups, self.c_out)

    @property
    def macs(self) -> int:
        """Multiply-accumulates for one image."""
        if self.kind == "dense":
            return self.c_in * self.c_out
        return (self.h_out * self.w_out * self.c_out
                * self.k * self.k * self.c_in // self.groups)

    @property
    def weight_words(self) -> int:
        n = 1
        for d in self.w_shape:
            n *= d
        return n + self.c_out

    @property
    def io_words(self) -> int:
        """Input plus output words of one image."""
        if self.kind == "dense":
            return self.c_in + self.c_out
        return (self.h_in * self.w_in * self.c_in
                + self.h_out * self.w_out * self.c_out)


def _plain(cfg: Dict) -> List[Op]:
    h, w, c = cfg["input"]
    out = []
    for cd in cfg["convs"]:
        op = Op(cd["name"], "conv", h, w, cd["c_in"], cd["c_out"], cd["k"],
                cd["stride"], cd["pad"], cd["groups"])
        out.append(op)
        h, w = op.h_out // cd["pool"], op.w_out // cd["pool"]
    return out


def _resnet(cfg: Dict) -> List[Op]:
    h, w, c = cfg["input"]
    st = cfg["stem"]
    stem = Op(st["name"], "conv", h, w, c, st["c_out"], st["k"],
              st["stride"], st["pad"])
    out = [stem]
    # the stem pool runs over the map padded by one row and column
    h, w = (stem.h_out + 1) // st["pool"], (stem.w_out + 1) // st["pool"]
    c = st["c_out"]
    for si, sg in enumerate(cfg["stages"]):
        for b in range(sg["blocks"]):
            s = sg["stride"] if b == 0 else 1
            pre = f"s{si + 2}b{b + 1}"
            a = Op(f"{pre}_1x1a", "conv", h, w, c, sg["c_mid"], 1, s)
            h2, w2 = a.h_out, a.w_out
            out += [a,
                    Op(f"{pre}_3x3", "conv", h2, w2, sg["c_mid"],
                       sg["c_mid"], 3, 1, 1),
                    Op(f"{pre}_1x1b", "conv", h2, w2, sg["c_mid"],
                       sg["c_out"], 1, 1)]
            if b == 0:
                out.append(Op(f"{pre}_proj", "conv", h, w, c, sg["c_out"],
                              1, s))
            h, w, c = h2, w2, sg["c_out"]
    return out


ARCHS = {"plain": _plain, "resnet_v1_bottleneck": _resnet}


def ops(cfg: Dict) -> List[Op]:
    """Every conv and dense op of one forward, in execution order."""
    convs = ARCHS[cfg["arch"]](cfg)
    return convs + [Op(f["name"], "dense", 1, 1, f["n"], f["m"])
                    for f in cfg["fcs"]]


def macs(cfg: Dict) -> int:
    return sum(op.macs for op in ops(cfg))


def params(cfg: Dict) -> int:
    return sum(op.weight_words for op in ops(cfg))


def flops(op: Op, k: int) -> float:
    return 2.0 * op.macs * k


def bytes_moved(op: Op, k: int) -> float:
    return float(WORD * (op.io_words * k + op.weight_words))


def least_seconds(op: Op, k: int, peaks: Dict) -> float:
    """The op's roofline floor for a call of `k` real rows."""
    return max(flops(op, k) / peaks["flops_bf16"],
               bytes_moved(op, k) / peaks["hbm_bytes_per_s"])


def net_least_seconds(cfg: Dict, k: int, peaks: Dict) -> float:
    return sum(least_seconds(op, k, peaks) for op in ops(cfg))


def net_flops(cfg: Dict, k: int) -> float:
    return sum(flops(op, k) for op in ops(cfg))
