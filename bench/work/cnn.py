"""Work of the CNN layer table: operations per image and, per conv
layer, the operations and least bytes of one batched call."""
from __future__ import annotations

from bench.work import itemsize


def walk(config: dict):
    """Yield (layer, (H, W, C) in, (H, W, C) out) along the table."""
    h = w = config["input_hw"]
    c = config["input_ch"]
    for layer in config["layers"]:
        kind = layer["kind"]
        if kind in ("conv", "maxpool", "avgpool"):
            ho = (h + 2 * layer["pad"] - layer["k"]) // layer["stride"] + 1
            wo = (w + 2 * layer["pad"] - layer["k"]) // layer["stride"] + 1
            co = layer["c_out"] if kind == "conv" else c
        else:
            ho, wo, co = 1, 1, layer["c_out"]
        yield layer, (h, w, c), (ho, wo, co)
        h, w, c = ho, wo, co


def layer_flops(config: dict) -> list[tuple[str, float]]:
    """(kind, multiply-add operations x 2) per conv and FC, per image."""
    out = []
    for layer, (h, w, c), (ho, wo, co) in walk(config):
        if layer["kind"] == "conv":
            out.append(("conv", 2.0 * ho * wo * co * layer["k"] ** 2 * c))
        elif layer["kind"] == "fc":
            out.append(("fc", 2.0 * h * w * c * co))
    return out


def image_flops(config: dict) -> float:
    return sum(f for _, f in layer_flops(config))


def conv_calls(config: dict, batch: int) -> list[tuple[float, float]]:
    """(operations, least bytes) of each conv layer over ``batch``
    images.  The bytes read the input and weights once and write the
    output once, after the pool that follows it where there is one (the
    least a conv with a fused pool has to write)."""
    elt = itemsize(config["dtype"])
    layers = list(walk(config))
    calls = []
    for i, (layer, (h, w, c), (ho, wo, co)) in enumerate(layers):
        if layer["kind"] != "conv":
            continue
        out_hw = ho * wo
        if i + 1 < len(layers) and layers[i + 1][0]["kind"] == "maxpool":
            ph, pw, _ = layers[i + 1][2]
            out_hw = ph * pw
        flops = 2.0 * batch * ho * wo * co * layer["k"] ** 2 * c
        nbytes = elt * (batch * h * w * c + layer["k"] ** 2 * c * co + co
                        + batch * out_hw * co)
        calls.append((flops, float(nbytes)))
    return calls
