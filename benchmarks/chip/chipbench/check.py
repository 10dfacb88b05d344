"""The comparison that decides ``correct``.

After the window has closed and the server is gone, a sample of the greedy
requests the server finished, drawn from the seed and always holding the
one with the most served tokens, is run once through the plain reference
(``reference.py``): each prompt with its served tokens, teacher-forced, in
float32 at full precision. At every served position the reference's logits
say how far the served token lies below their best, in units of the
reference logits' standard deviation at that position. Two numbers come of
it: the widest such gap over the sample (``logit_gap``) and their mean over
every served position (``mean_gap``); the configuration's ``check.limits``
says which are compared, each with its limit. A greedy server on the model
the checkpoint states reads 0, up to the rounding of its W4A8 arithmetic.

The control puts the reference in the program's place one precision step
lower (``act_bits=4``: FP4 activations where the configuration states FP8)
and reads, at the same positions, the gaps of the tokens the control puts
first (``control.logit_gap``, ``control.mean_gap``). The benchmark's own runs
do not compute it.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import reference

__all__ = ["Served", "plain_tree", "pick", "gaps"]


@dataclasses.dataclass(frozen=True)
class Served:
    prompt: tuple
    out: tuple


def plain_tree(tree):
    """The checkpoint as nested dicts of arrays: every packed linear as a
    dict of its fields, read by attribute (the reference imports nothing of
    the program)."""
    fields = ("codes", "s_max", "shifts", "lorc_a", "lorc_b")
    if all(hasattr(tree, f) for f in fields):
        return {f: getattr(tree, f) for f in fields}
    if isinstance(tree, dict):
        return {k: plain_tree(v) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return [plain_tree(v) for v in tree]
    return tree


def pick(served: Sequence[Served], seed: int, rows: int,
         tokens: int) -> List[Served]:
    """The request with the most served tokens, then others in an order
    drawn from the seed, until ``tokens`` served tokens or ``rows``
    requests are in the sample."""
    if not served:
        return []
    order = list(np.random.default_rng([seed, 2]).permutation(len(served)))
    longest = max(range(len(served)), key=lambda i: len(served[i].out))
    order.remove(longest)
    out, n = [served[longest]], len(served[longest].out)
    for i in order:
        if len(out) >= rows or n >= tokens:
            break
        out.append(served[i])
        n += len(served[i].out)
    return out


@partial(jax.jit, static_argnames=("arch_items",))
def _row_gaps(head, h_ref, h_ctl, nxt, arch_items):
    """Per position: the served token's gap below the reference's best,
    and the gap of the token the control puts first, both over the
    reference logits' standard deviation."""
    arch = dict(arch_items)
    lg = reference.logits(head, arch, h_ref)
    best = jnp.max(lg, axis=-1)
    sd = jnp.std(lg, axis=-1)
    served = jnp.take_along_axis(lg, nxt[:, None], axis=-1)[:, 0]
    ctl_tok = jnp.argmax(reference.logits(head, arch, h_ctl), axis=-1)
    ctl = jnp.take_along_axis(lg, ctl_tok[:, None], axis=-1)[:, 0]
    return (best - served) / sd, (best - ctl) / sd


def gaps(params, arch: Dict, sample: Sequence[Served], rows: int,
         seq: int, control: bool = False) -> Dict[str, float]:
    """``logit_gap`` and ``mean_gap`` (with ``control``, also the control's
    readings, prefixed ``control.``) over the sample, and the number of
    served tokens ``compared``. Each row is padded to ``seq`` tokens and the
    batch to ``rows`` rows, so every run shares one compiled reference."""
    if not sample:
        return {"compared": 0}
    plain = plain_tree(params)
    toks = np.zeros((rows, seq), np.int32)
    nxt = np.zeros((rows, seq), np.int32)
    mask = np.zeros((rows, seq), bool)
    for r, s in enumerate(sample):
        ctx = list(s.prompt) + list(s.out)
        toks[r, :len(ctx)] = ctx
        p = len(s.prompt)
        # logits at position p - 1 + i predict served token i
        nxt[r, p - 1:p - 1 + len(s.out)] = s.out
        mask[r, p - 1:p - 1 + len(s.out)] = True
    h_ref = reference.hidden(plain, arch, jnp.asarray(toks))
    h_ctl = (reference.hidden(plain, arch, jnp.asarray(toks), act_bits=4)
             if control else h_ref)
    head = reference._head(plain)
    items = reference._items(arch)
    served, ctl = [], []
    for r in range(len(sample)):
        g, c = _row_gaps(head, h_ref[r], h_ctl[r], jnp.asarray(nxt[r]), items)
        served.append(np.asarray(g)[mask[r]])
        ctl.append(np.asarray(c)[mask[r]])
    served, ctl = np.concatenate(served), np.concatenate(ctl)
    out = {"logit_gap": float(served.max()), "mean_gap": float(served.mean()),
           "compared": int(served.size)}
    if control:
        out["control.logit_gap"] = float(ctl.max())
        out["control.mean_gap"] = float(ctl.mean())
    return out
