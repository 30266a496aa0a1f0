"""Reader `trace-collectives`: seconds in which a collective ran on a
device and no other operation did, mean over the devices, as a share of
the traced window, in %. Nothing to read on one chip."""


def read(spec: dict, evidence: dict):
    tr = evidence.get("trace")
    if not tr or tr.get("collective_exposed_s") is None:
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["window_s"]
