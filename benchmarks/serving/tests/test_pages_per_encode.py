"""The ``page_seal.pages_per_encode`` reader on the tiny chat run of
``test_span_readers``: pages packed over encode calls in the window, and
nothing from a program without the counter or a window without an
encode."""
import dataclasses

import spec
from test_span_readers import ctx  # noqa: F401  (the module's fixture)


def read(c):
    return spec.load_reader("page_seal.pages_per_encode")(c)


def delta(c, key):
    return c.kv1[key] - c.kv0[key]


def test_reads_pages_per_encode_call(ctx):  # noqa: F811
    calls = delta(ctx, "kv_seal_encode_calls")
    pages = delta(ctx, "kv_pages_packed")
    assert calls > 0
    v = read(ctx)
    assert v == pages / calls
    # admissions seal every prompt page of both layers in a few calls
    assert v > 1


def test_reads_nothing_without_the_counter(ctx):  # noqa: F811
    def drop(kv):
        return {k: v for k, v in kv.items() if k != "kv_seal_encode_calls"}
    c = dataclasses.replace(ctx, kv0=drop(ctx.kv0), kv1=drop(ctx.kv1),
                            notes=[])
    assert read(c) is None and len(c.notes) == 1


def test_reads_nothing_without_an_encode(ctx):  # noqa: F811
    c = dataclasses.replace(ctx, kv1=ctx.kv0, notes=[])
    assert read(c) is None and len(c.notes) == 1
