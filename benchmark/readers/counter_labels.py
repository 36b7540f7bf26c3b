"""The sum of one of the program's counter families over the children whose
labels pass a filter, read from its metrics registry as it stands when the
run ends (``paddle_tpu.obs.metrics.REGISTRY``), as ``counter_ratio`` reads its
two: the harness's snapshots (``run.counters``) keep each family's total over
all its labels only.

params: ``name`` (the family), ``match`` (label -> the value a child must
have), ``exclude`` (label -> a value it must not have). The children that
passed are listed on a line before the result.

The counts are those since the process started. For the compile telemetry's
stage seconds that is the value when set-up ended, as long as the run's check
(c) holds: nothing is built inside a correct window.

None where the program has no such family (a tree from before PR 38), so the
metric is left out and is not 0."""


def read(params, run):
    from paddle_tpu.obs.metrics import REGISTRY

    family = REGISTRY.get(params["name"])
    if family is None:
        return None
    match, exclude = params.get("match", {}), params.get("exclude", {})
    passed = {}
    for key, child in family.children().items():
        labels = dict(zip(family.label_names, key))
        if all(labels.get(k) == v for k, v in match.items()) and not any(
                labels.get(k) == v for k, v in exclude.items()):
            passed[",".join(key)] = child.value
    run.notes.append(f"{params['name']} {match or ''} by child: " + (
        ", ".join(f"{k} {v:.3f}" for k, v in sorted(passed.items()))
        or "none"))
    return sum(passed.values())
