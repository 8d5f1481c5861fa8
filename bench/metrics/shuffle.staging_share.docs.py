"""Share of the prefill's device self time (``jit_serve_prefill``) in the
FUSCO staging scopes ``moe.dispatch`` and ``moe.combine``: on one chip the
descriptor gather that lands tokens expert-grouped and the gated
scatter-add that brings them back.  In percent; ``by_scope`` gives the
seconds of every scope."""

from bench import scopes as S


def read(record):
    return S.scope_share(record, "jit_serve_prefill",
                         ("moe.dispatch", "moe.combine"))
