"""Share of the device's busy time that a multiclass fit spends on its
objective's ``(K, n)`` softmax gradient and hessian: region ``class_grad``
over the busy seconds."""

from benchmark.metrics import _class


def read(ctx):
    return _class.region_share(ctx, "class_grad")
