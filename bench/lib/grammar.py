"""Which tokens the canonicalizer's constrained decoding may emit next.

The program decodes a signature under a character grammar: the text starts
with ``{``, stays at most ``MAX_CHARS`` long, never closes a bracket it has
not opened, and holds inside quotes only letters, digits and ``_.#- `` and
outside quotes only letters, digits and `` :,.-"`` besides the brackets.  A
token may follow a text when the text with it still keeps to that.  The
decoder takes, at each step, the legal token of the highest logit; the
logits check reads a served token against the best legal token of the
reference's row, so it needs this rule, restated here from the grammar.
"""
from __future__ import annotations

import string

import numpy as np

MAX_CHARS = 512
IN_STRING = set(string.ascii_lowercase + string.digits + "_.#- ")
OUTSIDE = set(' :,0-9.tfnue-"')


def _scan(state: tuple, text: str):
    """The state (chars, in_string, object depth, array depth) after
    ``text``, or None where ``text`` breaks the grammar."""
    n, in_str, objs, arrs = state
    if n + len(text) > MAX_CHARS:
        return None
    for ch in text:
        if n == 0 and ch != "{":
            return None
        n += 1
        if in_str:
            if ch == '"':
                in_str = False
            elif not (ch.isalnum() or ch in IN_STRING):
                return None
        elif ch == '"':
            in_str = True
        elif ch in "{}[]":
            objs += (ch == "{") - (ch == "}")
            arrs += (ch == "[") - (ch == "]")
            if objs < 0 or arrs < 0:
                return None
        elif ch not in OUTSIDE and not ch.isalnum():
            return None
    return n, in_str, objs, arrs


START = (0, False, 0, 0)


def legal(text: str, vocab: list[str]) -> np.ndarray:
    """Boolean mask over ``vocab`` (the token strings, '' for special
    tokens): the tokens that may follow ``text``."""
    state = _scan(START, text)
    if state is None:
        return np.zeros(len(vocab), bool)
    return np.asarray([bool(t) and _scan(state, t) is not None for t in vocab])
