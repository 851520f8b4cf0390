"""A trainer worker's own set-up: `train.worker_setup` (session, dataset
shards) + `train.compile` (`compile_train`: shardings, `eval_shape`, the
jit wrappers) + `train.init_state` (the first call of its `init_fn`: the
state made on the devices)."""

from . import _startup


def read(record):
    return _startup.total(
        record, ["train.worker_setup", "train.compile", "train.init_state"],
        pid=_startup.chip_pid(record))
