"""Share of the device's busy time that a multiclass fit spends moving its
``(K, n)`` scores by the new trees' leaf values: region ``class_update``
over the busy seconds."""

from benchmark.metrics import _class


def read(ctx):
    return _class.region_share(ctx, "class_update")
