"""The harness's clock around ``Dataset.construct`` less the sum of the
package's ``construct_phases``: construction time that no phase names."""


def read(ctx):
    total = ctx.spans.get("ingest.construct_s")
    named = [v for k, v in ctx.construct_phases.items()
             if k.endswith("_s") and isinstance(v, (int, float))]
    if total is None or not named:
        return None
    return total - sum(named)
