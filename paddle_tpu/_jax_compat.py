"""The one jax API this package reaches through a seam.

The code is written for the installed jax (0.9): `jax.shard_map(...,
check_vma=)`, `jax.lax.axis_size(name)` and `jax.profiler.ProfileData`
are called by their own names at every call site — there are no version
shims. What stays here is `profile_data()`, because
`observability.deviceprof` must never IMPORT jax itself (its parser also
runs in processes that stay off the chip): it finds this module through
`sys.modules`, i.e. only in a process that already has jax.
"""


def profile_data():
    """`load(path) -> ProfileData` for an `.xplane.pb` file."""
    from jax.profiler import ProfileData
    return ProfileData.from_file
