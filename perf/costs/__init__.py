"""Cost functions, one file per kernel or step: a later PR adds a
kernel's cost by adding ``perf/costs/<file>.py``; a per-layer metric
names the function as ``<file>.<function>``."""
import importlib.util
import os
from typing import Callable, Optional

_HERE = os.path.dirname(os.path.abspath(__file__))


def cost_function(name: str, perf_dir: Optional[str] = None
                  ) -> Callable:
    """``<file>.<function>`` under ``<perf_dir>/costs/`` (this
    checkout's by default), found by path."""
    module, _, fn = name.rpartition('.')
    path = os.path.join(perf_dir, 'costs', module + '.py') \
        if perf_dir else os.path.join(_HERE, module + '.py')
    spec = importlib.util.spec_from_file_location(
        'perf_cost_' + module, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, fn)
