"""A CNN classifier served as batches of images (``ServingEngine``'s
Program path for a ``CNNConfig``)."""
from __future__ import annotations

import numpy as np

from bench import drive
from bench.refs import cnn as cnn_ref
from bench.work import cnn as cnn_work

BLOCK_IMAGES = 32       # reference images per call


def check_registry(cfg, config: dict) -> None:
    got = {"input_hw": cfg.input_hw, "input_ch": cfg.input_ch,
           "n_classes": cfg.n_classes, "dtype": cfg.dtype,
           "layers": [_layer(l.kind, l.c_out, l.k, l.stride, l.pad,
                             l.activation) for l in cfg.layers]}
    want = {k: config[k] for k in got if k != "layers"}
    want["layers"] = [_layer(l["kind"], l.get("c_out", 0), l.get("k", 1),
                             l.get("stride", 1), l.get("pad", 0),
                             l.get("activation", "relu"))
                      for l in config["layers"]]
    if got != want:
        diff = {k: (got[k], want[k]) for k in got if got[k] != want[k]}
        raise SystemExit(f"bench: the program's {config['registry']!r} "
                         f"differs from the configuration file: {diff}")


def _layer(kind, c_out, k, stride, pad, activation) -> dict:
    """The fields that define a layer of this kind."""
    if kind == "maxpool":
        return {"kind": kind, "k": k, "stride": stride, "pad": pad}
    if kind == "fc":
        return {"kind": kind, "c_out": c_out, "activation": activation}
    return {"kind": kind, "c_out": c_out, "k": k, "stride": stride,
            "pad": pad, "activation": activation}


def build_engine(config: dict, traffic: dict, params, program=None):
    from repro.configs import get_config
    from repro.serving import ServingEngine
    cfg = get_config(config["registry"])
    check_registry(cfg, config)
    return ServingEngine(cfg, params, slots=traffic["slots"],
                         program=program)


def weight_shapes(config: dict):
    """{layer_ii: {"w", "b"}} for convs and FCs, with the (H, W, C) each
    layer sees worked out from the layer table."""
    out = {}
    for i, (layer, (h, w, c), (_, _, co)) in enumerate(
            cnn_work.walk(config)):
        if layer["kind"] == "conv":
            out[f"layer_{i:02d}"] = {"w": (layer["k"], layer["k"], c, co),
                                     "b": (co,)}
        elif layer["kind"] == "fc":
            out[f"layer_{i:02d}"] = {"w": (h * w * c, co), "b": (co,)}
    return out, config["dtype"], None


def make_inputs(traffic: dict, config: dict, rng) -> dict:
    """A pool of images; request i sends image i mod pool."""
    shape = (traffic["image_pool"], config["input_hw"], config["input_hw"],
             config["input_ch"])
    return {"images": rng.standard_normal(shape, dtype=np.float32)}


def request(t, uid: int):
    from repro.serving import Request
    i = uid % len(t.inputs["images"])
    return (Request(uid=uid, prompt=t.inputs["images"][i]),
            drive.Rec(uid, i, 0))


def warm_up(eng, t) -> None:
    """One batch: the only shape the window uses."""
    from repro.serving import Request
    images = t.inputs["images"]
    for i in range(t.slots):
        eng.submit(Request(uid=-1 - i, prompt=images[i % len(images)]))
    eng.step()


def prime(client, t) -> None:
    """Nothing: a batch carries no state from the one before."""


def capture(eng) -> list:
    """Keep the logits of every batch as the engine reads them back (it
    takes the host copy of the same array next, at no extra cost)."""
    infer, kept = eng._infer, []

    def keep(params, x):
        out = infer(params, x)
        kept.append(np.asarray(out))
        return out
    eng._infer = keep
    return kept


def on_output(tick, rec, before: int, after: int) -> None:
    tick.images += after - before
    tick.served.append((rec.item, rec.out[0]))


def compare(config: dict, params, t, w, rng, chk: dict, controls: tuple,
            captured: list) -> dict:
    """A sample of the window's batches, drawn from the seed, until
    ``chk["outputs"]`` images: the logits the engine served for them
    against the reference.  class_mismatch counts served classes that
    are not the argmax of the served logits (exact); logit_err is
    max |logits - ref| / max |ref|."""
    import jax
    import jax.numpy as jnp
    if len(captured) != len(w.ticks):
        raise RuntimeError(f"{len(captured)} batches kept for "
                           f"{len(w.ticks)} steps")
    ids, served, logits = [], [], []
    for k in rng.permutation(len(w.ticks)):
        if len(ids) >= chk["outputs"]:
            break
        tk = w.ticks[k]
        ids += [i for i, _ in tk.served]
        served += [c for _, c in tk.served]
        logits.append(captured[k][:len(tk.served)])
    if not ids:
        return {}
    logits = np.concatenate(logits).astype(np.float32)
    images = t.inputs["images"][np.array(ids)]
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)

    @jax.jit
    def block(p, x):
        with jax.default_matmul_precision("highest"):
            out = {"ref": cnn_ref.forward(config, p, x)}
            for q in controls:
                out[q] = cnn_ref.forward(config, p, x, quant=q)
        return out

    got = {k: [] for k in ("ref",) + tuple(controls)}
    for i in range(0, len(images), BLOCK_IMAGES):
        x = images[i:i + BLOCK_IMAGES]
        n = len(x)
        if n < BLOCK_IMAGES:                   # one compiled shape
            x = np.concatenate([x, np.repeat(x[:1], BLOCK_IMAGES - n, 0)])
        for k, v in block(params, jnp.asarray(x)).items():
            got[k].append(np.asarray(v)[:n])
    ref = np.concatenate(got["ref"])
    scale = float(np.abs(ref).max())

    def err(x):
        return float(np.abs(x - ref).max()) / scale
    out = {"class_mismatch": float(np.sum(np.argmax(logits, -1)
                                          != np.array(served))),
           "logit_err": err(logits)}
    for q in controls:
        out[f"control_logit_err.{q}"] = err(np.concatenate(got[q]))
    return out


def tick_flops(config: dict, tick) -> float:
    return tick.images * cnn_work.image_flops(config)
