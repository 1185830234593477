"""Glue: float32 MB per image that the served plan's glue writes between
its mapped layers (pads and crops, pools, concats), the program's host
counter ``NetworkPlan.glue_bytes_per_image``, counted from the static
shapes at plan compile.  The plan is the cell configuration's top tier,
compiled again through ``harness.build_mapping`` and
``exec.compile_plan`` (host only, memoised: the ladder's own plan).
None from a program whose ``GlueSpec`` refuses the configuration's glue
(a field or kind it lacks) or that has no counter; any other error is
raised."""

import harness


def read(run):
    from repro.exec import compile_plan
    try:
        harness.layer_glue(run.cell.config)
    except TypeError as e:              # a glue field the program lacks
        if "unexpected keyword argument" not in str(e):
            raise
        return None
    except ValueError as e:             # a glue kind or stage it lacks
        if not str(e).startswith("unknown glue"):
            raise
        return None
    plan = compile_plan(harness.build_mapping(run.cell.config),
                        executor_policy="mapped",
                        batch=int(run.cell.traffic["max_batch"]))
    n = getattr(plan, "glue_bytes_per_image", None)
    return None if n is None else n / 1e6
