"""Host time of the candidate feature build, per question (the span
around `batch_triple_features`; host clock)."""


def read(ctx):
    spans = ctx.spans.array("feature_build")
    if not len(spans) or spans[:, 2].sum() <= 0:
        return None
    return float((spans[:, 1] - spans[:, 0]).sum() / spans[:, 2].sum()) * 1e3
