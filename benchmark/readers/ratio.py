"""``scale * a / b``, each a ``"group.key"`` path into the observations:
``{"reader": "ratio", "num": "counters.admit_s", "den": "values.window_s",
"scale": 100}``. Optional ``"minus": "group.key"`` is subtracted from the
quotient before scaling (a difference of two means). Nothing to read, or a
zero denominator: no metric."""


def _get(obs: dict, path: str):
    group, key = path.split(".", 1)
    return (obs.get(group) or {}).get(key)


def read(spec: dict, obs: dict):
    num, den = _get(obs, spec["num"]), _get(obs, spec["den"])
    if num is None or not den:
        return None
    value = num / den
    if "minus" in spec:
        other = _get(obs, spec["minus"])
        if other is None:
            return None
        value -= other
    return spec.get("scale", 1) * value
