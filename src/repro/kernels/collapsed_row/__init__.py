from .ops import collapsed_row_flip
from .ref import collapsed_row_flip_ref
from .fast import collapsed_row_flip_fast

__all__ = [
    "collapsed_row_flip",
    "collapsed_row_flip_ref",
    "collapsed_row_flip_fast",
]
