"""Mean wall time of the window's mutation steps (host clock, from
`ctx.steps`): a step's inserts and deletes with the flushes and compaction
runs they set off. The compaction hook after each served batch is not part
of a step."""


def read(ctx):
    if not ctx.steps:
        return None
    return 1e3 * sum(st.end - st.start for st in ctx.steps) / len(ctx.steps)
