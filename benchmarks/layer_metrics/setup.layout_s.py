"""Host seconds set-up spent building the program's layouts and putting the
data on the device: ``attach_feature_major`` (GLM), ``GameEstimator``
construction and onboarding (GAME), ended by ``block_until_ready``."""


def read(run):
    return run["clock"].get("layout")
