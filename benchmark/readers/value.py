"""A number the kind measured itself on the host clock (``obs["values"]``)
or counted (``obs["counters"]``): ``{"reader": "value", "from": "values",
"key": "train_step_ms"}``."""


def read(spec: dict, obs: dict):
    return obs.get(spec.get("from", "values"), {}).get(spec["key"])
