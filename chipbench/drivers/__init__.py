"""One driver per kind of system under test: ``run(ctx) -> dict``."""
import importlib


def resolve(dotted: str):
    """The object a configuration or a cell names by dotted path."""
    mod, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(mod), attr)


def sizes(config: dict, workload: dict, rehearse: bool, defaults=None):
    """(builder arguments, traffic parameters) of a run: the files'
    published sizes over the driver's ``defaults``, or their
    ``rehearse`` overrides."""
    args = dict(config["builder"]["args"])
    traffic = dict(defaults or {}, **workload["traffic"])
    if rehearse:
        args.update(config["rehearse"]["builder_args"])
        traffic.update(workload.get("rehearse", {}))
    return args, traffic
