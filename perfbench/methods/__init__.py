"""Plan methods, one module each, found by the plan's "method" key as
`methods/<name>.py`. A method decides what a (K, N) site is under the
plan and supplies, for every model family:

* `KERNEL`: the kernel that serves the site, as the trace names it;
* `shapes(plan, k, n)`: the site's form under the plan (a dict that
  carries "method", "k" and "n");
* `arrays(key, shp)`: one layer of the site as the program holds it,
  made from `key` (`synth.site_arrays` folds in the site and layer);
* `node(arrays, shp, plan)`: those arrays in the program's node type;
* `linear(x, arrays, shp, act_wl)`: the plain float32 reference of the
  served linear;
* `weight_bytes(shp)` and `row_macs(shp)`: the HBM bytes of one layer of
  the site and the integer multiply-adds of one row through it.
"""
import importlib


def load(name: str):
    return importlib.import_module(f"methods.{name}")
