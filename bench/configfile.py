"""A benchmark configuration: its JSON file and the modules the file names.

The file holds the model's sizes under their published key names, and what
the harness needs to run it. Everything that depends on the model's layout
comes from the file itself or from modules it names, each a file under
``bench/configs/``, so that a configuration of another layout is added in
new files only:

``weights``       ``make_weights(config, seed)``: the weight tree, made on the
                  device from the seed, in the program's parameter layout
``reference``     ``logits(weights, config, tokens, rows, *, length, n_rows,
                  act_dtype=None)`` and ``Q_BLOCK``: the plain reference
``work``          ``dense_calls(config)``, ``decode_ops(config, context)``,
                  ``prompt_ops(config, start, n)``, ``head_ops(config)``:
                  the packed linears of a step and the model's operations
``program_keys``  {program config attribute (dotted for a nested one): file
                  key}: what the program's configuration has to agree on
``packed_op``     the packed weight op, or a list of them: each has to run
                  as the TPU kernel; the first is the dense linears' op
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

CONFIGS = Path(__file__).resolve().parent / "configs"


def module(config: dict, key: str):
    """The module that the configuration's ``key`` names, loaded from
    ``bench/configs/``."""
    path = CONFIGS / config[key]
    spec = importlib.util.spec_from_file_location(
        f"bench_{key}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def packed_ops(config: dict) -> list:
    """The configuration's packed weight ops, the dense linears' first."""
    op = config["packed_op"]
    return [op] if isinstance(op, str) else list(op)
