"""A dense decoder LM served token by token: prefill Program on
admission, then the decode Program reading the persistent KV cache
(``ServingEngine(use_program=True)``, contiguous KV, greedy)."""
from __future__ import annotations

import numpy as np

from bench import check, drive, traffic
from bench.refs import dense_lm as lm_ref
from bench.work import dense_lm as lm_work

BLOCK_SEQS = 2          # reference sequences per call

# What bench/refs/dense_lm.py implements, beside the file's numbers.
REF_SHAPE = {"family": "dense", "norm": "rmsnorm", "gated_mlp": True,
             "attn_window": None, "max_pos": 0}


def check_registry(cfg, config: dict) -> None:
    got = {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
           "num_attention_heads": cfg.n_heads,
           "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.hd,
           "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab,
           "rope_theta": cfg.rope_theta, "hidden_act": cfg.activation,
           "tie_word_embeddings": cfg.tie_embeddings,
           "torch_dtype": cfg.dtype,
           **{k: getattr(cfg, k) for k in REF_SHAPE}}
    want = {**{k: config.get(k) for k in got}, **REF_SHAPE}
    if got != want:
        diff = {k: (got[k], want[k]) for k in got if got[k] != want[k]}
        raise SystemExit(f"bench: the program's {config['registry']!r} "
                         f"differs from the configuration file: {diff}")


def build_engine(config: dict, traffic: dict, params, program=None):
    from repro.configs import get_config
    from repro.serving import ServingEngine
    cfg = get_config(config["registry"])
    check_registry(cfg, config)
    eng = ServingEngine(cfg, params, slots=traffic["slots"],
                        max_len=config["max_len"], use_program=True,
                        program=program)
    if not eng.on_program_path:
        raise SystemExit(f"bench: {config['name']} is not served on the "
                         f"Program path: {eng.fallback_reason}")
    return eng


def weight_shapes(config: dict):
    L, D = config["num_hidden_layers"], config["hidden_size"]
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    hd, F, V = config["head_dim"], config["intermediate_size"], \
        config["vocab_size"]
    tree = {
        "embed": (V, D),
        "blocks": {"attn_norm": (L, D), "wq": (L, D, H * hd),
                   "wk": (L, D, KV * hd), "wv": (L, D, KV * hd),
                   "wo": (L, H * hd, D), "mlp_norm": (L, D),
                   "w_gate": (L, D, F), "w_up": (L, D, F),
                   "w_down": (L, F, D)},
        "final_norm": (D,),
    }
    if not config["tie_word_embeddings"]:
        tree["lm_head"] = (D, V)
    return tree, config["torch_dtype"], "blocks/"


def in_flight(lengths: np.ndarray, n: int) -> tuple[np.ndarray, np.ndarray]:
    """(age, left) of ``n`` requests in flight in a steady backlog.

    In steady state the request a slot holds is drawn in proportion to
    its length, at an age uniform over it.  Laid end to end (shortest
    first), the requests' tokens form one line; ``n`` evenly spaced
    points on it each give one request and its age: the same ``n`` for
    every seed."""
    L = np.sort(lengths)
    ends = np.cumsum(L)
    pts = (np.arange(n) + 0.5) / n * ends[-1]
    i = np.searchsorted(ends, pts, side="right")
    age = np.floor(pts - (ends[i] - L[i])).astype(np.int64)
    return age, L[i] - age


def make_inputs(tspec: dict, config: dict, rng) -> dict:
    """``requests`` distinct backlog requests, and before them one
    request in flight per slot (``in_flight``): its prompt is a prompt
    of the mix followed by the ``age`` tokens it has been served, and it
    asks for the ``left`` tokens it still has to go.  Prompt and output
    lengths are paired by a fixed permutation, so every seed gets the
    same (prompt, output) pairs; the seed orders them and draws the
    token ids."""
    n, slots = tspec["requests"], tspec["slots"]
    plen = traffic.quantiles(tspec["prompt_len"], n)
    olen = traffic.quantiles(tspec["output_len"], n)
    if plen.max() + olen.max() > config["max_len"]:
        raise ValueError("prompt + output lengths exceed max_len")
    age, left = in_flight(olen, slots)
    open_len = traffic.quantiles(tspec["prompt_len"], slots)
    fixed = np.random.default_rng(0)
    open_len = open_len[fixed.permutation(slots)] + age
    olen = olen[fixed.permutation(n)]
    first, rest = rng.permutation(slots), rng.permutation(n)
    plen = np.concatenate([open_len[first], plen[rest]])
    max_new = np.concatenate([left[first], olen[rest]])
    tokens = rng.integers(0, config["vocab_size"], int(plen.sum()),
                          dtype=np.int32)
    return {"prompts": np.split(tokens, np.cumsum(plen)[:-1]),
            "max_new": [int(x) for x in max_new], "opening": slots}


def request(t, uid: int):
    """Requests 0 .. opening-1 are the ones in flight; the backlog
    after them repeats."""
    from repro.serving import Request
    k, m = t.inputs["opening"], len(t.inputs["prompts"])
    i = uid if uid < k else k + (uid - k) % (m - k)
    p = t.inputs["prompts"][i]
    return (Request(uid=uid, prompt=p, max_new_tokens=t.inputs["max_new"][i]),
            drive.Rec(uid, i, len(p)))


def warm_up(eng, t) -> None:
    """One prefill and one decode tick: every prefill is padded to
    max_len and every tick decodes all slots, so these are the only
    shapes."""
    from repro.serving import Request
    eng.submit(Request(uid=-1, prompt=t.inputs["prompts"][0],
                       max_new_tokens=2))
    eng.step()


def prime(client, t) -> None:
    """Admit the requests in flight: the window opens in steady state,
    with slots at staggered ages and admissions spread through it."""
    client.prime(t.inputs["opening"])


def capture(eng) -> None:
    """Nothing: the served tokens are what is compared."""


def on_output(tick, rec, before: int, after: int) -> None:
    if before == 0:
        tick.prefills.append(rec.prompt_len)
    # The token of index j >= 1 is decoded attending to prompt_len + j
    # rows (the prompt and the j tokens before it, the newest of which
    # it writes).
    tick.decodes.extend(rec.prompt_len + j
                        for j in range(max(before, 1), after))


def compare(config: dict, params, t, w, rng, chk: dict, controls: tuple,
            captured) -> dict:
    """A sample of the requests served, drawn from the seed, the one
    with most tokens first (``check.sample``).  The reference runs once
    over each prompt followed by its served tokens; a served token's gap
    is how far its logit lies below the reference's best at its
    position.  ``served_gap`` is the widest gap, ``mean_gap`` the mean
    over the served tokens (the one the int8 control fails, as its
    gaps are more often above 0).  The first token comes from the
    prefill Program, the rest from the decode Program reading the KV
    cache, so both and the cache writes between them are judged."""
    import jax
    import jax.numpy as jnp
    picked = check.sample(list(w.recs.values()), rng, chk["outputs"],
                          chk["max_requests"])
    if not picked:
        return {}
    seqs = [(t.inputs["prompts"][r.item], np.asarray(r.out, np.int32))
            for r in picked]
    S = config["max_len"]
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)

    @jax.jit
    def hid(p, toks):
        with jax.default_matmul_precision("highest"):
            out = {"ref": lm_ref.hidden(config, p, toks)}
            for q in controls:
                out[q] = lm_ref.hidden(config, p, toks, quant=q)
        return out

    def spread(gap, valid):
        """Sum, count and widest of the valid rows' gaps."""
        g = jnp.where(valid, gap, 0)
        return jnp.stack([jnp.sum(g), jnp.sum(valid), jnp.max(g)])

    @jax.jit
    def judge(p, h, rows, served, valid):
        with jax.default_matmul_precision("highest"):
            ref = lm_ref.logits(config, p, h["ref"][rows])
            out = {"": spread(check.gaps(ref, served), valid)}
            for q in controls:
                low = lm_ref.logits(config, p, h[q][rows], quant=q)
                out[q] = spread(check.gaps(ref, jnp.argmax(low, -1)), valid)
        return out

    tally: dict = {}
    for i in range(0, len(seqs), BLOCK_SEQS):
        block = seqs[i:i + BLOCK_SEQS]
        toks = np.zeros((BLOCK_SEQS, S), np.int32)
        for j, (prompt, served) in enumerate(block):
            seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
            if len(seq) > S:
                raise ValueError(f"sequence of {len(seq)} > max_len {S}")
            toks[j, :len(seq)] = seq
        h = hid(params, jnp.asarray(toks))
        for j, (prompt, served) in enumerate(block):
            n, L = len(served), len(prompt)
            rows = np.zeros(S, np.int32)          # one compiled shape
            rows[:n] = np.arange(L - 1, L - 1 + n)
            tgt = np.zeros(S, np.int32)
            tgt[:n] = served
            hj = {k: v[j] for k, v in h.items()}
            got = judge(params, hj, jnp.asarray(rows), jnp.asarray(tgt),
                        jnp.asarray(np.arange(S) < n))
            for k, v in got.items():
                v = np.asarray(v, np.float64)
                acc = tally.setdefault(k, np.array([0., 0., -np.inf]))
                acc[:2] += v[:2]
                acc[2] = max(acc[2], v[2])
    out = {}
    for k, (total, n, widest) in tally.items():
        pre = "control_" if k else ""
        post = f".{k}" if k else ""
        out[f"{pre}served_gap{post}"] = float(widest)
        out[f"{pre}mean_gap{post}"] = float(total / n)
    return out


def tick_flops(config: dict, tick) -> float:
    return (sum(lm_work.prefill_flops(config, n) for n in tick.prefills)
            + sum(lm_work.decode_token_flops(config, c)
                  for c in tick.decodes))
