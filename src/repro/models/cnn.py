"""The paper's CNNs: VGG-16, GoogleNet (Inception-v1), ResNet-50.

Single source of truth: each network is a list of *ops*; the same list is
(a) interpreted into a runnable JAX forward pass (NHWC,
``lax.conv_general_dilated`` or the Pallas conv kernel), and (b) flattened
into per-layer ``LayerTrace`` records (FLOPs + memory bytes) that feed the
statistical-traffic-shaping simulator (``repro.core.shaping_sim``).

Traces intentionally include the memory-bound "other filters" (BN, ReLU,
pooling) — the paper's Fig. 1 shows these interleaved phases are what drives
the bandwidth fluctuation.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BYTES = 4  # paper runs fp32 Caffe/MKL-DNN


# ---------------------------------------------------------------------------
# op tables
# ---------------------------------------------------------------------------

def _c(cout, k, s=1):
    return {"kind": "conv", "cout": cout, "k": k, "s": s}


def _mp(k=3, s=2):
    return {"kind": "maxpool", "k": k, "s": s}


def _fc(cout, relu=True):
    return {"kind": "fc", "cout": cout, "relu": relu}


def _inc(b1, b3r, b3, b5r, b5, bp):
    return {"kind": "inception", "b1": b1, "b3r": b3r, "b3": b3,
            "b5r": b5r, "b5": b5, "bp": bp}


def _rb(c1, c3, cout, s=1, proj=False):
    return {"kind": "resblock", "c1": c1, "c3": c3, "cout": cout,
            "s": s, "proj": proj}


def vgg16_ops():
    ops = []
    for cfgs in ([64, 64], [128, 128], [256, 256, 256],
                 [512, 512, 512], [512, 512, 512]):
        ops += [_c(c, 3) for c in cfgs]
        ops.append(_mp(2, 2))
    ops += [{"kind": "flatten"}, _fc(4096), _fc(4096), _fc(1000, relu=False)]
    return ops


def resnet50_ops():
    ops = [_c(64, 7, 2), _mp(3, 2)]
    stages = [(64, 256, 3, 1), (128, 512, 4, 2), (256, 1024, 6, 2),
              (512, 2048, 3, 2)]
    for cin, cout, n, s in stages:
        ops.append(_rb(cin, cin, cout, s=s, proj=True))
        ops += [_rb(cin, cin, cout) for _ in range(n - 1)]
    ops += [{"kind": "gap"}, _fc(1000, relu=False)]
    return ops


def googlenet_ops():
    return [
        _c(64, 7, 2), _mp(), _c(64, 1), _c(192, 3), _mp(),
        _inc(64, 96, 128, 16, 32, 32),      # 3a
        _inc(128, 128, 192, 32, 96, 64),    # 3b
        _mp(),
        _inc(192, 96, 208, 16, 48, 64),     # 4a
        _inc(160, 112, 224, 24, 64, 64),    # 4b
        _inc(128, 128, 256, 24, 64, 64),    # 4c
        _inc(112, 144, 288, 32, 64, 64),    # 4d
        _inc(256, 160, 320, 32, 128, 128),  # 4e
        _mp(),
        _inc(256, 160, 320, 32, 128, 128),  # 5a
        _inc(384, 192, 384, 48, 128, 128),  # 5b
        {"kind": "gap"}, _fc(1000, relu=False),
    ]


CNN_OPS = {"vgg16": vgg16_ops, "resnet50": resnet50_ops,
           "googlenet": googlenet_ops}
CNN_INPUT = {"vgg16": 224, "resnet50": 224, "googlenet": 224}


# ---------------------------------------------------------------------------
# per-layer traces (feeds the traffic-shaping simulator)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class LayerTrace:
    name: str
    kind: str           # conv | fc | bn | relu | pool | concat
    flops_per_img: float
    weight_bytes: float   # loaded once per (partition, batch) pass
    act_bytes_per_img: float  # read input + write output

    def bw_demand(self, batch, gflops_per_s):
        """Bandwidth demand (B/s) when compute-bound at given FLOP rate."""
        t = self.flops_per_img * batch / (gflops_per_s * 1e9)
        byt = self.weight_bytes + self.act_bytes_per_img * batch
        return byt / max(t, 1e-12)


def _conv_trace(name, H, W, cin, cout, k, s):
    Ho, Wo = -(-H // s), -(-W // s)
    flops = 2.0 * Ho * Wo * cout * cin * k * k
    wb = cout * cin * k * k * BYTES
    ab = (H * W * cin + Ho * Wo * cout) * BYTES
    return LayerTrace(name, "conv", flops, wb, ab), Ho, Wo


def trace_ops(ops, img=224, include_aux=True, with_bn=True) -> List[LayerTrace]:
    """Flatten an op list into LayerTrace records (order = execution order)."""
    H = W = img
    C = 3
    out: List[LayerTrace] = []

    def aux(name, kind, H, W, C, flo_per_el=1.0):
        if include_aux:
            el = H * W * C
            out.append(LayerTrace(name, kind, flo_per_el * el,
                                  2 * C * BYTES, 2 * el * BYTES))

    def conv(name, cin, cout, k, s, bn=with_bn, relu=True):
        nonlocal H, W
        t, Ho, Wo = _conv_trace(name, H, W, cin, cout, k, s)
        out.append(t)
        H, W = Ho, Wo
        if bn:
            aux(name + ".bn", "bn", H, W, cout, 2.0)
        if relu:
            aux(name + ".relu", "relu", H, W, cout, 1.0)
        return cout

    i = 0
    for op in ops:
        i += 1
        nm = f"op{i}"
        kind = op["kind"]
        if kind == "conv":
            C = conv(nm, C, op["cout"], op["k"], op["s"])
        elif kind == "maxpool":
            el = H * W * C
            out.append(LayerTrace(nm + ".pool", "pool", el * op["k"] ** 2,
                                  0.0, 2 * el * BYTES))
            H, W = -(-H // op["s"]), -(-W // op["s"])
        elif kind == "gap":
            out.append(LayerTrace(nm + ".gap", "pool", H * W * C, 0.0,
                                  (H * W * C + C) * BYTES))
            H = W = 1
        elif kind == "flatten":
            C = H * W * C
            H = W = 1
        elif kind == "fc":
            cin = H * W * C if H > 1 else C
            out.append(LayerTrace(nm + ".fc", "fc", 2.0 * cin * op["cout"],
                                  cin * op["cout"] * BYTES,
                                  (cin + op["cout"]) * BYTES))
            H = W = 1
            C = op["cout"]
        elif kind == "inception":
            cin = C
            Hs, Ws = H, W
            # four parallel branches, concat
            for branch, chain in {
                "b1": [(op["b1"], 1, 1)],
                "b3": [(op["b3r"], 1, 1), (op["b3"], 3, 1)],
                "b5": [(op["b5r"], 1, 1), (op["b5"], 5, 1)],
                "bp": [(op["bp"], 1, 1)],
            }.items():
                H, W = Hs, Ws
                c = cin
                if branch == "bp":
                    el = Hs * Ws * cin
                    out.append(LayerTrace(f"{nm}.{branch}.pool", "pool",
                                          el * 9, 0.0, 2 * el * BYTES))
                for j, (cout, k, s) in enumerate(chain):
                    c = conv(f"{nm}.{branch}.c{j}", c, cout, k, s, bn=with_bn)
            C = op["b1"] + op["b3"] + op["b5"] + op["bp"]
            H, W = Hs, Ws
            el = H * W * C
            out.append(LayerTrace(f"{nm}.concat", "concat", 0.0, 0.0,
                                  2 * el * BYTES))
        elif kind == "resblock":
            cin = C
            s = op["s"]
            conv(f"{nm}.c1", cin, op["c1"], 1, s)
            conv(f"{nm}.c3", op["c1"], op["c3"], 3, 1)
            conv(f"{nm}.cout", op["c3"], op["cout"], 1, 1, relu=False)
            if op["proj"]:
                # projection shortcut runs at the block's input resolution
                t, _, _ = _conv_trace(f"{nm}.proj", H * s, W * s, cin,
                                      op["cout"], 1, s)
                out.append(t)
                if with_bn:
                    aux(f"{nm}.proj.bn", "bn", H, W, op["cout"], 2.0)
            el = H * W * op["cout"]
            out.append(LayerTrace(f"{nm}.add", "relu", 2.0 * el, 0.0,
                                  3 * el * BYTES))
            C = op["cout"]
        else:
            raise ValueError(kind)
    return out


def model_traces(name: str, img: int | None = None) -> List[LayerTrace]:
    return trace_ops(CNN_OPS[name](), img or CNN_INPUT[name],
                     with_bn=(name != "vgg16"))


# ---------------------------------------------------------------------------
# runnable JAX forward (interprets the same op lists)
# ---------------------------------------------------------------------------


def _conv_init(key, k, cin, cout, dtype):
    fan = k * k * cin
    return (jax.random.normal(key, (k, k, cin, cout), jnp.float32)
            * math.sqrt(2.0 / fan)).astype(dtype)


def init_cnn(key, name, img=None, dtype=jnp.float32):
    """Returns (params list, static shapes probe)."""
    ops = CNN_OPS[name]()
    img = img or CNN_INPUT[name]
    params = []
    H = W = img
    C = 3
    for op in ops:
        key, sub = jax.random.split(key)
        kind = op["kind"]
        if kind == "conv":
            p = {"w": _conv_init(sub, op["k"], C, op["cout"], dtype),
                 "scale": jnp.ones((op["cout"],), dtype),
                 "shift": jnp.zeros((op["cout"],), dtype)}
            params.append(p)
            C = op["cout"]
            H, W = -(-H // op["s"]), -(-W // op["s"])
        elif kind == "maxpool":
            params.append({})
            H, W = -(-H // op["s"]), -(-W // op["s"])
        elif kind == "gap":
            params.append({})
            H = W = 1
        elif kind == "flatten":
            params.append({})
            C = H * W * C
            H = W = 1
        elif kind == "fc":
            cin = H * W * C if H > 1 else C
            p = {"w": (jax.random.normal(sub, (cin, op["cout"]), jnp.float32)
                       * math.sqrt(1.0 / cin)).astype(dtype),
                 "b": jnp.zeros((op["cout"],), dtype)}
            params.append(p)
            H = W = 1
            C = op["cout"]
        elif kind == "inception":
            ks = jax.random.split(sub, 6)
            p = {
                "b1": _conv_init(ks[0], 1, C, op["b1"], dtype),
                "b3r": _conv_init(ks[1], 1, C, op["b3r"], dtype),
                "b3": _conv_init(ks[2], 3, op["b3r"], op["b3"], dtype),
                "b5r": _conv_init(ks[3], 1, C, op["b5r"], dtype),
                "b5": _conv_init(ks[4], 5, op["b5r"], op["b5"], dtype),
                "bp": _conv_init(ks[5], 1, C, op["bp"], dtype),
            }
            params.append(p)
            C = op["b1"] + op["b3"] + op["b5"] + op["bp"]
        elif kind == "resblock":
            ks = jax.random.split(sub, 4)
            p = {"c1": _conv_init(ks[0], 1, C, op["c1"], dtype),
                 "c3": _conv_init(ks[1], 3, op["c1"], op["c3"], dtype),
                 "cout": _conv_init(ks[2], 1, op["c3"], op["cout"], dtype)}
            if op["proj"]:
                p["proj"] = _conv_init(ks[3], 1, C, op["cout"], dtype)
            params.append(p)
            C = op["cout"]
            H, W = -(-H // op["s"]), -(-W // op["s"])
        else:
            raise ValueError(kind)
    return params


def _conv2d(x, w, stride, conv_impl="xla", interpret=False):
    if conv_impl == "pallas":
        from repro.kernels.conv2d import ops as conv_ops
        return conv_ops.conv2d(x, w, stride=stride, padding="SAME",
                               interpret=interpret)
    return lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def apply_cnn(params, name, x, conv_impl="xla", interpret=False):
    """x: (B, H, W, 3) -> logits (B, 1000).  ``conv_impl="pallas"`` runs
    the convolutions through the Pallas kernel, interpreted when
    ``interpret`` is set (any backend) and compiled for the TPU otherwise."""
    ops = CNN_OPS[name]()
    conv = partial(_conv2d, conv_impl=conv_impl, interpret=interpret)
    for op, p in zip(ops, params):
        kind = op["kind"]
        if kind == "conv":
            x = conv(x, p["w"], op["s"])
            x = jax.nn.relu(x * p["scale"] + p["shift"])
        elif kind == "maxpool":
            x = lax.reduce_window(
                x, -jnp.inf, lax.max, (1, op["k"], op["k"], 1),
                (1, op["s"], op["s"], 1), "SAME")
        elif kind == "gap":
            x = x.mean(axis=(1, 2), keepdims=True)
        elif kind == "flatten":
            x = x.reshape(x.shape[0], 1, 1, -1)
        elif kind == "fc":
            x = x.reshape(x.shape[0], -1) @ p["w"] + p["b"]
            if op["relu"]:
                x = jax.nn.relu(x)
            x = x.reshape(x.shape[0], 1, 1, -1)
        elif kind == "inception":
            b1 = jax.nn.relu(conv(x, p["b1"], 1))
            b3 = jax.nn.relu(conv(
                jax.nn.relu(conv(x, p["b3r"], 1)),
                p["b3"], 1))
            b5 = jax.nn.relu(conv(
                jax.nn.relu(conv(x, p["b5r"], 1)),
                p["b5"], 1))
            bp = lax.reduce_window(
                x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 1, 1, 1), "SAME")
            bp = jax.nn.relu(conv(bp, p["bp"], 1))
            x = jnp.concatenate([b1, b3, b5, bp], axis=-1)
        elif kind == "resblock":
            h = jax.nn.relu(conv(x, p["c1"], op["s"]))
            h = jax.nn.relu(conv(h, p["c3"], 1))
            h = conv(h, p["cout"], 1)
            sc = conv(x, p["proj"], op["s"]) if "proj" in p else x
            x = jax.nn.relu(h + sc)
    return x.reshape(x.shape[0], -1)
