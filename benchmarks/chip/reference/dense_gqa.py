"""Dense decoder with grouped-query attention: Llama, Yi and Qwen2.

Two things live here, and neither imports the program under test.

**Weights.** Every weight is drawn from ``--seed`` by ``leaf(...)``: uniform
bits from a key folded from the seed, the leaf's name and its layer, turned
into a bfloat16 value by integer arithmetic and one float32 multiply, so the
same call gives the same bits in any program that makes it. The harness
makes the served weights in one jitted call (``program_params``), in the
program's layout and in bfloat16, the type they are served in; the
reference makes each layer's weights again, from the seed, when it needs
them (``layer_weights``), and never reads what the program holds.

Scales are chosen so that random weights do not make a degenerate model:
the output head is tied to the embedding, so the current token's own
embedding in the residual stream favours repeating it. The layers'
outputs are scaled (``out_std``) so that the residual stream is mostly
theirs, and the current token gains about 1.5 standard deviations of logit
rather than winning every step. Queries and keys are scaled so that
attention scores have a standard deviation near 2: attention is peaked
enough that a wrong position or a stale cache row changes the output.

**Reference.** The architecture's equations in float32 ``jax.numpy`` at
``Precision.HIGHEST``, one sequence and one layer at a time, with no cache,
no batching and no padding that reaches a real position:

    h_0      = E[x_t] * sqrt(d)                      (the program's convention)
    a        = RMSNorm(h, 1 + g_1)
    q, k, v  = a W_q + b_q, a W_k + b_k, a W_v + b_v (biases: Qwen2 only)
    q, k     = RoPE(q, t), RoPE(k, t)                (rotate-half, base theta)
    o_t      = sum_s<=t softmax_s(q_t . k_s / sqrt(hd)) v_s
                                      (kv head of q head j: j // (H / KV))
    h        = h + o W_o
    m        = RMSNorm(h, 1 + g_2)
    h        = h + (silu(m W_g) * (m W_i)) W_o'
    logits   = RMSNorm(h, 1 + g_f) E^T               (tied, as the program)

Departures from the published models, all forced by the program and noted
in each configuration's ``assumed``: the embedding is multiplied by
sqrt(d); the head is tied to the embedding; norm gains are stored as
offsets from 1.

``mode="fp8"`` is the control: the same equations with every weight matrix
rounded to float8 (e4m3) with one scale per output channel, and every
activation that enters a weight product (the embedding rows and the head
included) rounded to float8 with one scale per token; the rest in float32.
It stands for the step below the configurations' bfloat16 that would tempt
a later change: fp8 weights and activations.
"""
from __future__ import annotations

import functools
import math
import zlib
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NORM_STD = 0.1          # norm gains are 1 + N(0, 0.1)-like offsets
QK_STD = 1.5            # q and k entries ~1.5: scores with std ~2.25
BIAS_STD = 0.5
SEQ_BLOCK = 512         # sequences and positions are padded to a multiple,
                        # so that few shapes compile
VOCAB_BLOCK = 16384     # most rows of the head converted to f32 at once


def dims(model: dict) -> SimpleNamespace:
    """The sizes the reference needs, from a configuration's ``model``."""
    mult = int(model.get("vocab_pad_multiple", 256))
    vocab = int(model["vocab_size"])
    return SimpleNamespace(
        d=int(model["d_model"]), L=int(model["num_layers"]),
        H=int(model["num_heads"]), KV=int(model["num_kv_heads"]),
        hd=int(model["head_dim"]), ff=int(model["d_ff"]), vocab=vocab,
        padded_vocab=-(-vocab // mult) * mult,
        eps=float(model.get("norm_eps", 1e-6)),
        theta=float(model.get("rope_theta", 10000.0)),
        qkv_bias=bool(model.get("qkv_bias", False)))


def out_std(m) -> float:
    """Scale of the layers' output projections (see the module doc)."""
    return math.sqrt(m.d / (2 * m.L)) / 1.5


def layer_leaves(m) -> dict:
    """Leaf path (program layout, without the layer axis) -> (shape, std)."""
    d, H, KV, hd, ff = m.d, m.H, m.KV, m.hd, m.ff
    a = out_std(m)
    leaves = {
        "ln1": ((d,), NORM_STD),
        "attn.wq": ((d, H, hd), QK_STD / math.sqrt(d)),
        "attn.wk": ((d, KV, hd), QK_STD / math.sqrt(d)),
        "attn.wv": ((d, KV, hd), 1.0 / math.sqrt(d)),
        "attn.wo": ((H, hd, d), a / math.sqrt(H * hd)),
        "ln2": ((d,), NORM_STD),
        "mlp.wi": ((d, ff), 1.0 / math.sqrt(d)),
        "mlp.wg": ((d, ff), 1.0 / math.sqrt(d)),
        "mlp.wo": ((ff, d), 2.0 * a / math.sqrt(ff)),
    }
    if m.qkv_bias:
        leaves.update({"attn.bq": ((H, hd), BIAS_STD),
                       "attn.bk": ((KV, hd), BIAS_STD),
                       "attn.bv": ((KV, hd), BIAS_STD)})
    return leaves


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds need more than 32 bits)."""
    s = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(np.uint32(s & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(s >> 32))


def leaf(key, name: str, layer, shape, std: float):
    """One weight, bit for bit the same wherever it is made: uniform with
    standard deviation ``std``, in bfloat16."""
    k = jax.random.fold_in(jax.random.fold_in(
        key, np.uint32(zlib.crc32(name.encode()))), layer)
    bits = jax.random.bits(k, shape, jnp.uint32)
    centred = (bits >> 8).astype(jnp.int32) - (1 << 23)
    step = np.float32(math.sqrt(3.0) * std * 2.0 ** -23)
    return (centred.astype(jnp.float32) * step).astype(jnp.bfloat16)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *parents, last = path.split(".")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = v
    return out


def _layer_flat(m, key, i) -> dict:
    return {n: leaf(key, n, i, shape, std)
            for n, (shape, std) in layer_leaves(m).items()}


def _embed(m, key):
    return leaf(key, "embed.tok", 0, (m.padded_vocab, m.d),
                1.0 / math.sqrt(m.d))


def _final_norm(m, key):
    return leaf(key, "final_norm", 0, (m.d,), NORM_STD)


@functools.lru_cache(maxsize=None)
def _jitted(kind: str, m_items: tuple, device=None):
    m = SimpleNamespace(**dict(m_items))
    if kind == "program":
        def fn(key):
            stacked = jax.lax.map(lambda i: _layer_flat(m, key, i),
                                  jnp.arange(m.L, dtype=jnp.uint32))
            return {"embed": {"tok": _embed(m, key)},
                    "blocks": [_nest(stacked)],
                    "final_norm": _final_norm(m, key)}
        sharding = (jax.sharding.SingleDeviceSharding(device)
                    if device is not None else None)
        return jax.jit(fn, out_shardings=sharding)
    if kind == "layer":
        return jax.jit(lambda key, i: _layer_flat(m, key, i))
    if kind == "embed":
        return jax.jit(lambda key: _embed(m, key))
    if kind == "final_norm":
        return jax.jit(lambda key: _final_norm(m, key))
    raise ValueError(kind)


def _items(m) -> tuple:
    return tuple(sorted(vars(m).items()))


def program_params(model: dict, seed: int, device=None):
    """The served weights, in the program's parameter layout, made on
    ``device`` in one jitted call from the seed."""
    m = dims(model)
    return _jitted("program", _items(m), device)(seed_key(seed))


def layer_weights(m, key, i: int) -> dict:
    return _nest(_jitted("layer", _items(m))(key, np.uint32(i)))


# ---------------------------------------------------------------------------
# The reference
# ---------------------------------------------------------------------------


FP8_MAX = 448.0          # largest finite float8_e4m3fn


def _quant(a, axes):
    """Round to float8 e4m3, one scale per slice over ``axes`` that maps
    the slice's largest magnitude to the format's largest."""
    s = jnp.max(jnp.abs(a), axis=axes, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, x, w, x_axes, w_axes, lowp: bool):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if lowp:
        x, w = _quant(x, x_axes), _quant(w, w_axes)
    return jnp.einsum(eq, x, w, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, g, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g.astype(jnp.float32))


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = jnp.asarray(
        1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd),
        jnp.float32)
    ang = pos[:, None].astype(jnp.float32) * freqs          # (T, hd/2)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(m, lowp: bool, W, h):
    t = h.shape[0]
    pos = jnp.arange(t)
    a = _rms(h, W["ln1"], m.eps)
    at = W["attn"]
    q = _mm("td,dhk->thk", a, at["wq"], (1,), (0,), lowp)
    k = _mm("td,dhk->thk", a, at["wk"], (1,), (0,), lowp)
    v = _mm("td,dhk->thk", a, at["wv"], (1,), (0,), lowp)
    if m.qkv_bias:
        q = q + at["bq"].astype(jnp.float32)
        k = k + at["bk"].astype(jnp.float32)
        v = v + at["bv"].astype(jnp.float32)
    q, k = _rope(q, pos, m.theta), _rope(k, pos, m.theta)
    g = m.H // m.KV
    qg = q.reshape(t, m.KV, g, m.hd)
    s = jnp.einsum("tkgh,skh->kgts", qg, k, precision=HIGHEST) \
        / math.sqrt(m.hd)
    s = jnp.where(pos[None, None, None, :] <= pos[None, None, :, None],
                  s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgts,skh->tkgh", p, v,
                   precision=HIGHEST).reshape(t, m.H, m.hd)
    h = h + _mm("thk,hkd->td", o, at["wo"], (1, 2), (0, 1), lowp)
    b = _rms(h, W["ln2"], m.eps)
    ml = W["mlp"]
    gate = _mm("td,df->tf", b, ml["wg"], (1,), (0,), lowp)
    up = _mm("td,df->tf", b, ml["wi"], (1,), (0,), lowp)
    return h + _mm("tf,fd->td", jax.nn.silu(gate) * up, ml["wo"], (1,), (0,),
                   lowp)


def _vocab_block(m) -> int:
    rows = m.padded_vocab // 256
    best = 1
    for b in range(1, rows + 1):
        if rows % b == 0 and 256 * b <= VOCAB_BLOCK:
            best = b
    return 256 * best


def _head(m, lowp: bool, E, gf, h, idx):
    """Logits (n, vocab) of the final hidden states ``h[idx]``."""
    hn = _rms(h[idx], gf, m.eps)
    blk = _vocab_block(m)
    Eb = E.reshape(m.padded_vocab // blk, blk, m.d)
    out = jax.lax.map(
        lambda e: _mm("nd,vd->nv", hn, e, (1,), (1,), lowp), Eb)
    return jnp.moveaxis(out, 0, 1).reshape(idx.shape[0], -1)[:, :m.vocab]


@functools.lru_cache(maxsize=None)
def _ref_fns(m_items: tuple, lowp: bool):
    m = SimpleNamespace(**dict(m_items))

    def embed(E, toks):
        e = E[toks].astype(jnp.float32)
        if lowp:
            e = _quant(e, (1,))
        return e * np.float32(math.sqrt(m.d))

    return (jax.jit(embed), jax.jit(functools.partial(_layer, m, lowp)),
            jax.jit(functools.partial(_head, m, lowp)))


def logits_at(model: dict, seed: int, seqs, positions,
              modes=("f32",)) -> dict:
    """Reference logits. ``seqs[j]`` is the token sequence fed and
    ``positions[j]`` the positions whose next-token logits are wanted.
    Returns mode -> list of (len(positions[j]), vocab) float32 numpy arrays.
    Weights are made again from ``seed``, one layer at a time."""
    m = dims(model)
    items = _items(m)
    key = seed_key(seed)
    E = _jitted("embed", items)(key)
    gf = _jitted("final_norm", items)(key)
    fns = {mode: _ref_fns(items, mode == "fp8") for mode in modes}
    padded = []
    for s in seqs:
        n = -(-len(s) // SEQ_BLOCK) * SEQ_BLOCK
        padded.append(np.pad(np.asarray(s, np.int32), (0, n - len(s))))
    hs = {mode: [fns[mode][0](E, jnp.asarray(p)) for p in padded]
          for mode in modes}
    for i in range(m.L):
        W = layer_weights(m, key, i)
        for mode in modes:
            hs[mode] = [fns[mode][1](W, h) for h in hs[mode]]
        del W
    out = {}
    for mode in modes:
        out[mode] = []
        for h, idx in zip(hs[mode], positions):
            n = len(idx)
            pad = np.full(-(-n // SEQ_BLOCK) * SEQ_BLOCK, idx[-1], np.int32)
            pad[:n] = idx
            out[mode].append(np.asarray(fns[mode][2](E, gf, h, pad))[:n])
    return out


def fed_and_positions(prompt, served):
    """The sequence the reference is run over for one request and the
    positions whose logits choose each served token."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    fed = np.concatenate([prompt, served[:-1]])
    return fed, np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
