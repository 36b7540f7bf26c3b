"""One labelled count of the program over another, read from its metrics
registry as it stands (``paddle_tpu.obs.metrics.REGISTRY``).

params: ``numerator`` and ``denominator`` (each ``name`` + ``labels``) and
``scale``. The counts are those since the process started: the harness's
snapshots before and after the window (``run.counters``) keep each family's
total over its labels only, so the change over the window cannot be split by
label here; set-up adds the few batches it stages to the thousand-odd of
the window.

None where the program has no such family (one from before PR 26) or the
denominator is 0."""


def _value(spec):
    from paddle_tpu.obs.metrics import REGISTRY

    family = REGISTRY.get(spec["name"])
    return None if family is None else family.labels(**spec["labels"]).value


def read(params, run):
    num, den = _value(params["numerator"]), _value(params["denominator"])
    if num is None or not den:
        return None
    return float(params.get("scale", 1.0)) * num / den
