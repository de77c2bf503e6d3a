"""How the benchmark's tests hold ``BENCHMARK.json``: an entry by its name,
wherever it stands in its list, and the cells of a ``workloads`` list by
membership. A later PR appends entries and joins cells to lists; neither
moves or changes what a test here asserts."""

SECTIONS = ("per_layer", "end_to_end")


def entry(spec: dict, name: str) -> dict:
    """The one metric of ``spec`` named ``name``, in either section."""
    found = [m for sec in SECTIONS for m in spec[sec] if m["name"] == name]
    assert len(found) == 1, f"{name}: {len(found)} entries"
    return found[0]


def check_entry(spec: dict, name: str, cells, **fields) -> None:
    """Assert that the entry ``name`` has exactly the keys ``name``,
    ``workloads`` and those of ``fields``; that each of ``fields`` has the
    value given; that every cell of ``cells`` is in its ``workloads``
    list; and that no cell is listed twice."""
    m = entry(spec, name)
    keys = {"name", "workloads", *fields}
    assert set(m) == keys, (name, sorted(set(m) ^ keys))
    for key, want in fields.items():
        assert m[key] == want, (name, key, m[key], want)
    check_cells(m, cells)


def check_cells(m: dict, cells) -> None:
    """Assert that every cell of ``cells`` is in the entry's ``workloads``
    list and that no cell is listed twice; other cells may be listed."""
    listed = m["workloads"]
    assert len(listed) == len(set(listed)), (m["name"], listed)
    missing = [c for c in cells if c not in listed]
    assert not missing, (m["name"], missing)


def listing(spec: dict, cell: str) -> set:
    """The names of the metrics, of both sections, that list ``cell``."""
    return {m["name"] for sec in SECTIONS for m in spec[sec]
            if cell in m.get("workloads", [])}
