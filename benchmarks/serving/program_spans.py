"""The program's own spans (``repro.runtime.spans``) in a traced run's
window, for the readers in ``metrics/``.

``window(ctx, metric)`` returns a ``Spans`` over the spans that start in
``ctx.window``, or None, after a line in ``ctx.notes``, where the program
records no spans (a checkout without ``repro.runtime.spans``) or its ring
has dropped some of the window's spans.
"""
from __future__ import annotations

from collections import defaultdict


class Spans:
    def __init__(self, spans: list):
        self.spans = spans
        self._children = defaultdict(list)
        for s in spans:
            if s.parent is not None:
                self._children[s.parent].append(s)

    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]

    def under(self, top, name: str) -> list:
        """The spans named ``name`` nested, at any depth, in ``top``."""
        out, todo = [], list(self._children[top.id])
        while todo:
            s = todo.pop()
            if s.name == name:
                out.append(s)
            todo.extend(self._children[s.id])
        return sorted(out, key=lambda s: s.t0)


def seconds(spans: list) -> float:
    return sum(s.t1 - s.t0 for s in spans)


def window(ctx, metric: str) -> Spans | None:
    try:
        from repro.runtime import spans
    except ImportError:
        ctx.notes.append(f"{metric}: the program records no spans")
        return None
    rec = spans.recorded(*ctx.window)
    if not rec.complete:
        ctx.notes.append(f"{metric}: the program's span ring dropped spans "
                         f"of the window; not read")
        return None
    return Spans(rec.spans)
