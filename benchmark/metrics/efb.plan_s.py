"""``Dataset.construct_phases['efb_plan_s']`` (the package's own clock) where
the plan bundled something (the ``efb_plan`` event's ``bundles``): the 50
k-row sample drawn, binned and searched for exclusive columns.
``ingest.efb_plan_s``'s reading; its list of cells is closed."""
from benchmark import scopes_multiclass as mc


def read(ctx):
    ev = mc.last_event(ctx, "efb_plan")
    if not (ev and ev["bundles"]):
        return None
    return ctx.construct_phases.get("efb_plan_s")
