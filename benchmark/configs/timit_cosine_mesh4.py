"""TimitPipeline across the chips of a mesh: `timit_cosine`'s own data
and `build_pipeline`, at the sizes of `timit_cosine_mesh4.json`. The mesh
is not this file's: `benchmark.run` builds it from the cell's `chips` and
`make_data` places every `Dataset` on it, sharded by rows."""

from .timit_cosine import build, make_data  # noqa: F401
