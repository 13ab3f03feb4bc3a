"""Driver of a served configuration whose layer stack is looped
(Ouro): ``serve_engine`` in every respect - the same engine build,
warm-up, load, window and comparison - but for what is built: the
weights carry the leaves the architecture adds
(``perf/lib/weights_looped.py``), and the program's model has to loop
as the configuration file says (``total_ut_steps``,
``early_exit_threshold``)."""
import functools
from typing import Any, Dict

from perf.drivers import serve_engine as base
from perf.lib import harness
from perf.lib import weights_looped


def program_config(config: Dict[str, Any]):
    """The program's model, checked against the file's published
    keys: the widths (``harness.program_config``) and the loop. A
    program without the model, or without a loop, cannot run the
    configuration and says so before it touches a device."""
    name = config['program_model']
    try:
        prog = harness.program_config(config)
    except KeyError:
        raise harness.HarnessError(
            f'the program has no model {name!r}: this tree cannot '
            f'run the configuration') from None
    model = config['model']
    got = {'total_ut_steps': getattr(prog, 'loop_passes', 1),
           'early_exit_threshold': getattr(prog, 'exit_threshold',
                                           None)}
    wrong = {k: (model[k], v) for k, v in got.items()
             if model[k] != v}
    if wrong or not getattr(prog, 'sandwich_norms', False):
        raise harness.HarnessError(
            f'the program\'s {name!r} does not loop as the '
            f'configuration file says (file, program): {wrong}; '
            f'branch norms: {getattr(prog, "sandwich_norms", None)}')
    return prog


class Served(base.Served):
    """``serve_engine.Served`` on this architecture's weights."""

    def __init__(self, loaded: Dict[str, Any], seed: int,
                 rehearse: bool):  # pylint: disable=super-init-not-called
        import jax
        from skypilot_tpu.serve.batching import BatchingEngine
        from skypilot_tpu.utils import jax_runtime

        config = loaded['config']
        self.prog = program_config(config)
        self.device = harness.require_devices(
            loaded['cell']['chips'], rehearse)
        jax_runtime.configure_compile_cache()
        self.model = config['model']
        self.build = config['build']
        self.traffic = loaded['traffic']
        self.params, _ = weights_looped.make_weights(
            self.model, seed, int8=config['weights'] == 'int8',
            dtype=self.prog.dtype)
        self.engine = BatchingEngine(self.params, self.prog,
                                     **self.build)
        base._warm_up(self.engine, self.model['vocab_size'],
                      self.engine.prefill_chunk, seed)
        jax.block_until_ready(self.engine.caches)


def _on_this_system(fn):
    """``serve_engine.run`` and ``control_readings`` build the system
    under test as ``serve_engine.Served``, by that name: run them with
    the name bound to this module's."""
    @functools.wraps(fn)
    def call(*args, **kwargs):
        theirs, base.Served = base.Served, Served
        try:
            return fn(*args, **kwargs)
        finally:
            base.Served = theirs
    return call


run = _on_this_system(base.run)
control_readings = _on_this_system(base.control_readings)
