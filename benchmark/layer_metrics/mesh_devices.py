"""Device: the devices the last train's sweep laid its rows over, the gauge
``mesh.devices`` the program sets where it places them: as many as the
configuration has row shards, or the run was not the deployment."""

LAYER = "device"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "train_wall_s"


def read(ctx):
    from transmogrifai_tpu.telemetry import REGISTRY
    devices = REGISTRY.gauge("mesh.devices").value
    return float(devices) if devices else None
