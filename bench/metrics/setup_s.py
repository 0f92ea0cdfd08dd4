"""Process start to window start: imports, compile cache, growing every
study through ask/tell, the cold full refit and the first incremental
round (host clock)."""


def read(run):
    return run.setup_s
