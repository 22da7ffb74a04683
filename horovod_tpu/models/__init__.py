from ..common import metrics as _metrics

with _metrics.span("import:horovod_tpu.models"):
    from . import inception, resnet, vgg  # noqa: F401
