"""Process start to the window's opening: imports, building the trainer,
compiling (or loading) both ``chunk_step`` variants and ``reset_all``, and
the warm-up episodes."""


def read(record):
    if record.get("opened") is None:
        return None
    return record["opened"] - record["t_start"]
