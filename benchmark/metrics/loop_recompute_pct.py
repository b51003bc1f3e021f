"""Model runtime — ``ops/modules.py::Looped``: the share of the loop's block
applications that the backward recomputes, from the counters of the job's
``penroz/loop_plan`` span (``recomputed_applications`` ÷ ``applications``;
``kinds/train_looped.py`` reads the span).  100 where every application
keeps only its input; what a program that keeps some activations would
lower.  A program without the span gives nothing to read."""


def read(art):
    plan = art.get("loop_plan")
    if not plan or not plan.get("applications"):
        return None
    return 100.0 * plan["recomputed_applications"] / plan["applications"]
