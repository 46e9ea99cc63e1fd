"""The windowed difference of one server counter over that of another, or
over a count of operations the clients completed (`ops:<kind>`). A counter
the server publishes only as a rate (`rate:<name>`) counts as its mean
rate over the window times the window."""


def delta(observed: dict, name: str):
    if name.startswith("ops:"):
        return observed.get("ops", {}).get(name[4:])
    if name.startswith("rate:"):
        rate = observed.get("rates", {}).get(name[5:])
        return None if rate is None else rate * observed["window_s"]
    c = observed.get("counters")
    if not c or name not in c["after"]:
        return None
    return c["after"][name] - c["before"].get(name, 0)


def read(observed: dict, params: dict):
    num, den = delta(observed, params["num"]), delta(observed, params["den"])
    if num is None or not den:
        return None
    return params.get("scale", 1.0) * num / den
