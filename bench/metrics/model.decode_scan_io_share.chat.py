"""Share of the pool decode's device self time (``jit_serve_decode``) in
``layers.scan_io``: the layer scan's own work outside every finer named
scope, chiefly each layer's slice of the stacked parameters.  In percent;
``by_scope`` gives the seconds of every scope."""

from bench import scopes as S


def read(record):
    return S.scope_share(record, "jit_serve_decode", (S.SCAN_IO,))
