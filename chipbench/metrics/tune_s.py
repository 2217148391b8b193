"""Host-clock seconds of the trainer's startup DPT tune."""


def read(run):
    return run.tune_s if run.tune_trials else None
