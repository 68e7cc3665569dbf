"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample of
the requests it finished, drawn from the seed and always holding the
longest, is run through the float32 reference of the configuration's
architecture file (``bench/archs/<arch>.py``, ``logits_at``) over each
prompt followed by its served tokens.  For every served token
the number compared is how far its reference logit lies below the
reference's best at that position; the run is correct while the widest of
those gaps stays under the configuration's limit.  Served tokens are
greedy, so a correct engine differs from the reference only by its
bfloat16 rounding.

The control puts the reference in the program's place one precision
lower (float8 operands, ``reference.fp8``): at each position of the same
sequences it reads the gap of the token the lower precision puts first.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference


def gap_fn(arch, config: dict, control: bool = False):
    """Jitted ``(w, tokens (S,), rows (R,), served (R,), live (R,)) ->
    widest gap``, at ``highest`` precision, on the reference of the
    architecture file ``arch`` for ``config``.  With ``control`` the
    served tokens are ignored and the float8 reference's own choices are
    judged instead."""
    hf, settings = config["published"], config["architecture"]

    def gaps(w, tokens, rows, served, live):
        ref = arch.logits_at(hf, settings, w, tokens, rows)
        if control:
            low = arch.logits_at(hf, settings, w, tokens, rows,
                                 reference.fp8)
            served = jnp.argmax(low, axis=-1)
        chosen = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
        g = jnp.max(ref, axis=-1) - chosen
        return jnp.max(jnp.where(live, g, -jnp.inf))

    fn = jax.jit(gaps)

    def run(*args):
        with jax.default_matmul_precision("highest"):
            return float(fn(*args))
    return run


def sequence(prompt: np.ndarray, served: list[int], seq_len: int,
             rows_len: int):
    """Inputs for one request: the prompt and every served token but the
    last, padded to ``seq_len``; the positions that predicted each served
    token, padded to ``rows_len``."""
    n = len(served)
    seq = np.zeros(seq_len, np.int32)
    body = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    if len(body) > seq_len or n > rows_len:
        raise ValueError(f"request of {len(body)} tokens and {n} served "
                         f"does not fit {seq_len}/{rows_len}")
    seq[:len(body)] = body
    rows = np.zeros(rows_len, np.int32)
    rows[:n] = len(prompt) - 1 + np.arange(n)
    tok = np.zeros(rows_len, np.int32)
    tok[:n] = served
    live = np.arange(rows_len) < n
    return seq, rows, tok, live


def sample(finished: list, n: int, seed: int, by_replica: bool) -> list:
    """``n`` of the ``finished`` (record, request) pairs, drawn from the
    seed: the longest, then (with ``by_replica``) one from each replica,
    then the rest at random."""
    if not finished:
        return []
    rng = np.random.default_rng([seed % 2**64, 4])
    order = list(rng.permutation(len(finished)))
    longest = max(range(len(finished)),
                  key=lambda i: finished[i][0].prompt_len
                  + finished[i][0].n_tokens)
    picked = [longest]
    if by_replica:
        seen = {finished[longest][0].replica}
        for i in order:
            rep = finished[i][0].replica
            if rep not in seen:
                seen.add(rep)
                picked.append(i)
    for i in order:
        if len(picked) >= n:
            break
        if i not in picked:
            picked.append(i)
    return [finished[i] for i in picked]


def widest_gap(fn, weights, pairs, seq_len: int, rows_len: int) -> float:
    worst = 0.0
    for _, req in pairs:
        seq, rows, tok, live = sequence(np.asarray(req.prompt),
                                        list(req.out_tokens), seq_len,
                                        rows_len)
        worst = max(worst, fn(weights, jnp.asarray(seq), jnp.asarray(rows),
                              jnp.asarray(tok), jnp.asarray(live)))
    return worst
