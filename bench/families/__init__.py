"""What differs between model families: one module each, ``bench/families/<family>.py``.

A configuration file's ``family`` names its module.  A module gives:

* ``stated_sizes(file, cfg, model)``: {key: program's value} for the
  family's own keys of the configuration file;
* ``init_params(model, key)``: the weights, drawn on the device in one
  jitted call;
* ``vocab(file)``, ``max_total(file)``: the token ids a prompt draws from,
  and the largest prompt + output served uncut;
* ``warmup_prompts(file, plan)``: (prompt length, new tokens) pairs whose
  serving compiles every program the plan can reach;
* ``kv_snapshot(engine, records, layers)``: what the engine caches for
  in-flight requests, for the check (``None`` where the family has none);
* ``reference_kwargs(file)``: keyword arguments of the plain reference;
* ``matmul_params(file)``, ``head_params(file)``: weights one token
  multiplies through in the layers and in the head;
* ``Reference``: the plain reference (``bench/reference``).

Adding a family adds a module here and its reference; no file changes.
"""
import importlib


def load(name: str):
    return importlib.import_module(f"{__name__}.{name}")
