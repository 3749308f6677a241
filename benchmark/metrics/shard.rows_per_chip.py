"""Rows a chip holds under the row grid the trainer ADOPTED, as its
``shard_plan`` event says (``rows_per_shard``: 36,750,000 where 147 M rows
lie over four chips). Ingest may change the grid it first published after a
device fault (more shards, or one); ``correct`` passes such a run, this
number shows it: all the rows on one chip read 147,000,000."""


def read(ctx):
    plans = [e for e in ctx.obs_events if e.get("type") == "shard_plan"]
    return plans[-1]["rows_per_shard"] if plans else None
