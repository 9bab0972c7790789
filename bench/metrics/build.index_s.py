"""Host clock around `repro.core.build_index` (Vamana, PQ, layout, shuffle,
MemGraph); its outputs are numpy arrays, so the call is synchronous."""


def read(ctx):
    return ctx.build_s
