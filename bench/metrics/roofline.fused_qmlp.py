"""``fused_qmlp``'s share of its roofline (``opcount.roofline_share``)."""
import opcount


def read(ctx):
    """``fused_qmlp``'s roofline share, in %."""
    return opcount.roofline_share(ctx, "fused_qmlp")
