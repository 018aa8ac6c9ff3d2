"""Peak device memory of the training process after its window, in GB: the
fullest chip's ``peak_bytes_in_use`` plus ``peak_bytes_reserved`` of
``memory_stats()`` (arrays, plus what loaded programs hold for their
temporaries; runners/__init__.py::peak_of)."""


def read(records):
    if not records.get("peak_bytes"):
        return None
    return records["peak_bytes"] / 1e9
