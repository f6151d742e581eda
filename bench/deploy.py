"""Build a cell's deployment: data, fitted model, compiled model; and its
plain reference.

The configuration file names its ``kind``; ``bench/kinds/<kind>.py`` fits,
digests, stores and compiles that kind of model and builds its reference.
The fit is held to the digest in the configuration file: a run whose model
differs fails, so no change to the program can swap the deployment unseen.

Fitted and compiled models are kept under ``bench/cache/`` (git-ignored),
keyed on the training data, the fit parameters and the source of the code
that fits and compiles, so only a checkout's first run of a configuration
pays for them.
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import inspect
import json
import os
import pickle
from pathlib import Path

import numpy as np

import data

BENCH = Path(__file__).resolve().parent
CACHE = BENCH / "cache"


class DigestMismatch(RuntimeError):
    pass


@dataclasses.dataclass
class Deployment:
    config: dict
    model: object            # the fitted model (tree arrays or estimator)
    compiled: object         # what the program serves
    X_test: np.ndarray       # the rows requests are drawn from
    cached: dict             # {"fit": bool, "compiled": bool}: found cached


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kind_module(kind: str):
    return load_module(BENCH / "kinds" / f"{kind}.py")


def source_digest(*modules) -> str:
    """Digest of the source of the given modules (a package counts with every
    ``.py`` file in its directory)."""
    h = hashlib.sha256()
    for mod in modules:
        path = Path(inspect.getsourcefile(mod))
        files = (sorted(path.parent.glob("*.py")) if path.name == "__init__.py"
                 else [path])
        for f in files:
            h.update(f.read_bytes())
    return h.hexdigest()


def _cached(path: Path, make) -> tuple:
    """(object, whether it came from the cache)."""
    if path.exists():
        with open(path, "rb") as fh:
            return pickle.load(fh), True
    obj = make()
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp")
    with open(tmp, "wb") as fh:
        pickle.dump(obj, fh, protocol=pickle.HIGHEST_PROTOCOL)
    os.replace(tmp, path)
    return obj, False


def build(config: dict, *, check_digest: bool = True) -> Deployment:
    kind = kind_module(config["kind"])
    Xtr, ytr, Xte, _ = data.load_split(config["dataset"])
    h = hashlib.sha256(Xtr.tobytes() + ytr.tobytes())
    h.update(json.dumps(config["fit"], sort_keys=True).encode())
    h.update(kind.trainer_digest().encode())
    fit_key = h.hexdigest()[:16]
    name = config["name"]
    model, fit_hit = _cached(CACHE / f"{name}-fit-{fit_key}.pkl",
                             lambda: kind.fit(config, Xtr, ytr))
    digest = kind.digest(model)
    if check_digest and digest != config["digest"]:
        raise DigestMismatch(
            f"{name}: fitted model digest {digest} differs from the "
            f"configuration's {config['digest']}")
    h = hashlib.sha256((digest + str(config["s"])).encode())
    h.update(kind.compiler_digest().encode())
    compiled, compiled_hit = _cached(
        CACHE / f"{name}-compiled-{h.hexdigest()[:16]}.pkl",
        lambda: kind.compile(model, config))
    return Deployment(config=config, model=model, compiled=compiled,
                      X_test=Xte,
                      cached={"fit": fit_hit, "compiled": compiled_hit})


def reference(dep: Deployment):
    """The deployment's plain reference (built after the window: its cost
    is no part of set-up)."""
    return kind_module(dep.config["kind"]).reference(dep.model, dep.config)
