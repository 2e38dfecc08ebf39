"""Host seconds set-up spent making the data from the seed."""


def read(run):
    return run["clock"].get("data")
