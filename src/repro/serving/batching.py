"""Static batched generation: one prefill + ONE fused decode dispatch.

This is the shared core the v0 ``examples/serve.py`` and
``launch/serve.py`` both hand-rolled as a per-token Python loop (one
device dispatch + host sync per generated token). Here the whole decode
runs as a single jitted ``lax.scan`` - one dispatch per ``max_new``
tokens - and the same routine serves as (a) the demo/launcher generate,
(b) the benchmarks' static-batch baseline, and (c) the engine's
bit-identity reference (``generate_reference``).

The prefill is the engine's (``runners.prefill_rows``/``write_rows``): a
buffer of prompt rows cut to a length class, run on a scratch cache with
the head on each row's last position, then written into the decode rows.

Bit-identity mechanics (measured on the CPU backend, pinned by
``tests/test_serving.py``): per-ROW float results are invariant to the
other rows' contents at a FIXED batch shape, but not across shapes: a
(1,d)x(d,e) decode matmul is NOT bitwise a row of the (N,d)x(d,e) one
(gemv vs gemm accumulation order, ~6e-7 drift), and a prefill cut to 4
positions is not bitwise the first rows of one cut to 8.
``generate_reference`` therefore prefills the single request alone in
row 0 of ``arrival_slots`` rows at the engine's length class and decodes
it in row 0 of a batch PADDED to the engine's slot count - the engine
step's shapes, so equality is structural.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.serving.runners import prefill_class, prefill_lengths, \
    prefill_rows, write_rows

Array = jax.Array


def request_key(base_key: Array, req_id) -> Array:
    """Per-request sampling stream, independent of slot/tick placement."""
    return jax.random.fold_in(base_key, req_id)


def sample_token(logits: Array, key: Array, temperature: float) -> Array:
    """(.., V) f32 logits -> int32 token. ``temperature`` is a static
    Python float: 0.0 means greedy argmax (no key consumed)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits.astype(jnp.float32) / temperature
    if logits.ndim == 1:
        return jax.random.categorical(key, scaled).astype(jnp.int32)
    keys = jax.random.split(key, logits.shape[0])
    return jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)


def _token_key(base_key: Array, req_id: Array, token_idx) -> Array:
    return jax.random.fold_in(request_key(base_key, req_id), token_idx)


def _row_sample(logits: Array, base_key: Array, req_id: Array, token_idx,
                temperature: float) -> Array:
    """Per-row sampling with per-(request, token) keys: row ``b`` draws
    from ``fold_in(fold_in(base, req_id[b]), token_idx[b])`` - the key
    depends only on WHICH request and WHICH token, never on the slot or
    tick it happens to occupy, so the engine and the reference consume
    identical streams."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    token_idx = jnp.broadcast_to(token_idx, req_id.shape)
    keys = jax.vmap(_token_key, in_axes=(None, 0, 0))(base_key, req_id,
                                                      token_idx)
    return jax.vmap(jax.random.categorical)(
        keys, logits.astype(jnp.float32) / temperature).astype(jnp.int32)


def _prefill(runner, params, caches, prompts, plens, req_ids, base_key,
             temperature):
    """Prefill the first ``R`` of ``B`` decode rows from ``prompts``
    (R, L), as the engine admits a tick's arrivals; rows past ``B`` are
    inert. Returns (token 0 per decode row, caches)."""
    r, b = prompts.shape[0], plens.shape[0]
    k = min(r, b)
    taken = jnp.arange(r) < k
    pre_plen = jnp.ones((r,), jnp.int32).at[:k].set(plens[:k])
    pre_req = jnp.full((r,), -1, jnp.int32).at[:k].set(req_ids[:k])
    logits, fresh = prefill_rows(runner, params, caches, prompts, pre_plen)
    caches = write_rows(caches, fresh, jnp.minimum(jnp.arange(r), b - 1),
                        taken)
    tok = _row_sample(logits.astype(jnp.float32), base_key, pre_req,
                      jnp.zeros((r,), jnp.int32), temperature)
    return jnp.zeros((b,), jnp.int32).at[:k].set(tok[:k]), caches


def _prefill_buffer(prompts, plens, rows: Optional[int] = None,
                   length: Optional[int] = None):
    """The engine's prefill buffer for a static batch: ``prompts`` (b, P)
    cut to ``length`` (default: the smallest class of
    ``runners.prefill_lengths(P)`` that holds the longest prompt) and
    zero-padded to ``rows`` rows (default ``b``)."""
    prompts = jnp.asarray(prompts, jnp.int32)
    b, p = prompts.shape
    if length is None:
        length = prefill_lengths(p)[prefill_class(p, int(jnp.max(plens)))]
    rows = b if rows is None else rows
    keep = min(length, p)
    return jnp.zeros((rows, length), jnp.int32).at[:b, :keep].set(
        prompts[:, :keep])


def make_generate_fn(runner, *, max_new: int, temperature: float = 0.0):
    """Build the fused static generate: ONE prefill + ONE decode scan.

    Returned ``generate(params, caches, prompts, plens, gen_targets,
    req_ids, base_key)``:

    * ``prompts`` (R, L): the prefill buffer (:func:`_prefill_buffer`),
      row ``i`` the zero-padded prompt of decode row ``i``; decode rows
      past ``R`` are inert padding;
    * ``plens`` (B,) true lengths, ``gen_targets`` (B,) tokens wanted per
      row (<= max_new); rows stop advancing once done (their KV writes
      freeze in place, masked);
    * returns ``(tokens (B, max_new) int32, n_gen (B,))``.

    Jit this (it is one trace for all call sites); the decode scan is the
    satellite "fold the per-token Python loop into one dispatch".
    """

    def generate(params, caches, prompts, plens, gen_targets, req_ids,
                 base_key):
        b = plens.shape[0]
        tok, caches = _prefill(runner, params, caches, prompts, plens,
                               req_ids, base_key, temperature)
        buf0 = jnp.zeros((b, max_new), jnp.int32).at[:, 0].set(tok)
        active0 = gen_targets > 1

        def step(carry, _):
            caches, tok, pos, n_gen, active, buf = carry
            logits, caches = runner.decode(params, tok[:, None], caches, pos)
            nxt = _row_sample(logits.astype(jnp.float32), base_key, req_ids,
                              n_gen, temperature)
            tok = jnp.where(active, nxt, tok)
            buf = jax.vmap(
                lambda row, t, i: jax.lax.dynamic_update_slice(row, t[None], (i,))
            )(buf, tok, jnp.clip(n_gen, 0, max_new - 1))
            # frozen rows keep their old buf rows: re-select
            buf = jnp.where(active[:, None], buf, carry[5])
            pos = jnp.where(active, pos + 1, pos)
            n_gen = jnp.where(active, n_gen + 1, n_gen)
            active = active & (n_gen < gen_targets)
            return (caches, tok, pos, n_gen, active, buf), None

        n0 = jnp.ones((b,), jnp.int32)
        carry = (caches, tok, plens, n0, active0, buf0)
        (caches, tok, pos, n_gen, active, buf), _ = jax.lax.scan(
            step, carry, None, length=max_new - 1)
        return buf, n_gen

    return generate


def generate_static(runner, params, prompts, plens, gen_targets, *,
                    max_new: int, temperature: float = 0.0,
                    base_key=None, req_ids=None, cache_len=None,
                    pad_rows: Optional[int] = None,
                    arrival_slots: Optional[int] = None,
                    prefill_len: Optional[int] = None):
    """Convenience one-shot static generate (builds caches, jits, runs).

    ``pad_rows``: pad the batch with inert rows up to this total so the
    decode matmuls have the same shape as an engine with that many slots
    (see module docstring); returns only the real rows.
    ``arrival_slots`` / ``prefill_len``: the prefill buffer's rows and
    length (:func:`_prefill_buffer`), as an engine with that many arrival
    slots prefills a tick at that length class.
    """
    prompts = jnp.asarray(prompts, jnp.int32)
    plens = jnp.asarray(plens, jnp.int32)
    gen_targets = jnp.asarray(gen_targets, jnp.int32)
    b, p = prompts.shape
    pre = _prefill_buffer(prompts, plens, arrival_slots, prefill_len)
    if req_ids is None:
        req_ids = jnp.arange(b, dtype=jnp.int32)
    req_ids = jnp.asarray(req_ids, jnp.int32)
    if base_key is None:
        base_key = jax.random.PRNGKey(0)
    n_real = b
    if pad_rows is not None and pad_rows > b:
        pad = pad_rows - b
        plens = jnp.concatenate([plens, jnp.ones((pad,), jnp.int32)])
        gen_targets = jnp.concatenate(
            [gen_targets, jnp.ones((pad,), jnp.int32)])
        req_ids = jnp.concatenate(
            [req_ids, jnp.full((pad,), -1, jnp.int32)])
        b = pad_rows
    if cache_len is None:
        cache_len = p + max_new
    caches = runner.init_caches(b, cache_len)
    gen = jax.jit(make_generate_fn(runner, max_new=max_new,
                                   temperature=temperature))
    buf, n_gen = gen(params, caches, pre, plens, gen_targets, req_ids,
                     base_key)
    return buf[:n_real], n_gen[:n_real]


def generate_reference(runner, params, prompt, *, gen_target: int,
                       max_new: int, prompt_pad: int, slots: int,
                       arrival_slots: int, prefill_len: Optional[int] = None,
                       temperature: float = 0.0, base_key=None,
                       req_id: int = 0, cache_len=None):
    """THE single-request reference path: one request, alone, prefilled
    in row 0 of an ``arrival_slots``-row buffer at ``prefill_len`` (the
    length class of the engine tick that admitted it; default: the class
    of its own length), written into row 0 of a ``slots``-row batch (the
    other rows are inert padding) and decoded there. Engine outputs must
    match this bitwise per request."""
    prompt = jnp.asarray(prompt, jnp.int32)
    pl = prompt.shape[0]
    padded = jnp.zeros((1, prompt_pad), jnp.int32).at[0, :pl].set(prompt)
    toks, n_gen = generate_static(
        runner, params, padded, jnp.array([pl], jnp.int32),
        jnp.array([gen_target], jnp.int32), max_new=max_new,
        temperature=temperature, base_key=base_key,
        req_ids=jnp.array([req_id], jnp.int32), cache_len=cache_len,
        pad_rows=slots, arrival_slots=arrival_slots, prefill_len=prefill_len)
    return toks[0, :int(n_gen[0])]


def decode_python_loop(runner, params, prompts, plens, gen_targets, *,
                       max_new: int, temperature: float = 0.0,
                       base_key=None, req_ids=None, cache_len=None):
    """The v0 per-token host loop (one jitted dispatch + host sync per
    token). Kept ONLY as the benchmark "before" for the fused-scan
    satellite; produces the same tokens as :func:`generate_static`."""
    prompts = jnp.asarray(prompts, jnp.int32)
    plens = jnp.asarray(plens, jnp.int32)
    gen_targets = jnp.asarray(gen_targets, jnp.int32)
    b, p = prompts.shape
    if req_ids is None:
        req_ids = jnp.arange(b, dtype=jnp.int32)
    if base_key is None:
        base_key = jax.random.PRNGKey(0)
    if cache_len is None:
        cache_len = p + max_new
    caches = runner.init_caches(b, cache_len)

    prefill = jax.jit(lambda params, caches, pre: _prefill(
        runner, params, caches, pre, plens, req_ids, base_key, temperature))
    decode = jax.jit(runner.decode)
    sample = jax.jit(lambda lg, n: _row_sample(
        lg.astype(jnp.float32), base_key, req_ids, n, temperature))

    tok, caches = prefill(params, caches, _prefill_buffer(prompts, plens))
    buf = [tok]
    pos = plens
    for i in range(1, max_new):
        logits, caches = decode(params, tok[:, None], caches, pos)
        active = jnp.asarray(i, jnp.int32) < gen_targets
        nxt = sample(logits, jnp.full((b,), i, jnp.int32))
        tok = jnp.where(active, nxt, tok)
        buf.append(jnp.where(active, tok, 0))
        pos = jnp.where(active, pos + 1, pos)
        jax.block_until_ready(tok)  # the v0 loop's per-token host sync
    toks = jnp.stack(buf, axis=1)
    n_gen = jnp.minimum(gen_targets, max_new)
    mask = jnp.arange(max_new)[None, :] < n_gen[:, None]
    return jnp.where(mask, toks, 0), n_gen
