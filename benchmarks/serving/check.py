"""The comparison that decides ``correct``.

Once the window has closed, every request that was served a token in the
window is run once through the plain reference over its prompt and the
tokens it was served.  Each served token is greedy, so at its position
the reference should rank it first up to rounding.  The number compared is
``served_logit_gap``: the widest gap, over every served token, by which the
reference's logit of that token lies below the reference's best logit.

With ``control``, the reference computed in the next lower precision stands
in the program's place: at the same positions of the same sequences, the
gap of the token that it puts first (``control_logit_gap``) is the number
compared.
"""
from __future__ import annotations

import numpy as np

import reference


def _positions(prompt, tokens):
    """The sequence the reference reads, and where each served token is
    predicted: token i of ``tokens`` follows position len(prompt)-1+i."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(tokens[:-1])])
    pos = len(prompt) - 1 + np.arange(len(tokens))
    return seq.astype(np.int32), pos


def served_gaps(model: dict, w: dict, served: list, pad_to: int,
                control: str | None = None) -> dict:
    """Widest gap of the served tokens (and, with ``control``, of the
    control's first-ranked tokens) below the reference's best logit."""
    gap = ctl = 0.0
    n = 0
    for prompt, tokens in served:
        if not len(tokens):
            continue
        seq, pos = _positions(prompt, tokens)
        targets = np.zeros((len(seq), 2), np.int32)
        targets[pos, 0] = tokens
        if control is not None:
            top = reference.read(model, w, seq, targets[:, :1], pad_to,
                                 mode=control)[2]
            targets[:, 1] = top
        best, got, _ = reference.read(model, w, seq, targets, pad_to)
        gap = max(gap, float(np.max(best[pos] - got[pos, 0])))
        ctl = max(ctl, float(np.max(best[pos] - got[pos, 1])))
        n += len(pos)
    out = {"served_logit_gap": gap, "positions": n}
    if control is not None:
        out["control_logit_gap"] = ctl
    return out
