"""A number the runner itself took in the traced run, by its place in
what the runner observed (`path`: keys from the top down)."""


def read(observed: dict, params: dict):
    value = observed
    for key in params["path"]:
        if not isinstance(value, dict) or key not in value:
            return None
        value = value[key]
    return value
