"""Columns the binned matrix holds after bundling, as the program's
``efb_plan`` event says (``columns_out``: 54 raw columns with 44 one-hot
ones come out as about 14). Silent where nothing was bundled, and from a
program that emits no such event."""
from benchmark import scopes_multiclass as mc


def read(ctx):
    ev = mc.last_event(ctx, "efb_plan")
    return ev["columns_out"] if ev and ev["bundles"] else None
