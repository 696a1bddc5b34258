"""Pages the index selected for the window's batch unions, as a share of the
table pages those batches could see (``EngineStats.selected_pages`` over
``table_pages_seen``): how much of the table Hippo's summaries leave to
inspect."""


def read(ctx):
    if not ctx.table_pages_seen:
        return None
    return 100.0 * ctx.selected_pages / ctx.table_pages_seen
