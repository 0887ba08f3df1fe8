"""Model families, one module each, found by the configuration's
"family" key as `families/<name>.py`. A family supplies everything that
depends on the architecture:

* `program_config(config)`: the program's `ModelConfig`, nested config
  types included, from the configuration file's contents;
* `program_tree(seed, cfg)`: the served parameter tree, made on the
  device in one jitted call;
* `hidden_states(seed, cfg, seqs, lower=None)` and `logits(seed, cfg, h)`:
  the plain float32 reference at "highest", one layer at a time across
  sequences;
* `work(cfg, tokens, steps, seed)`: {kernel name as the trace names it:
  {"ops", "bytes", "peak"}} of every kernel the family's step runs over a
  window, "peak" a key of `peaks.json`. `tokens` is `work.Tokens`: the
  window's valid rows as counts, and by request with token ids and the
  step each row ran in, so work that depends on routing can run the
  family's reference over the ids from `seed` and group rows by step.

`cfg` is the configuration's "model" keys with its "plan" and "family".
What every family shares (W4 codes, row quantization, norms, positions,
token counts, the output head's reference, and every plan method of
`methods/`) is in `synth`, `reference` and `work`: a site's form comes
from `synth.shapes(cfg["plan"], k, n)`, so every family serves every
method.
"""
import importlib


def load(name: str):
    return importlib.import_module(f"families.{name}")
