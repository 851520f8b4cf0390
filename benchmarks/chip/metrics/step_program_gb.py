"""`compiled.memory_analysis()` of the train step, per device: temporaries
+ arguments + outputs - aliased, in GB (1e9 bytes)."""


def read(record):
    return record["program_bytes"]["train_step"]["total"] / 1e9
