"""The one thing a new stream cell cannot bring by a file of its own:
``test_bm_catalog.py`` holds the list of host-fed cells as a constant
(``STREAM_CELLS``), and ``test_two_rates_for_two_regimes`` wants
``stream_ex_per_s`` to list exactly those. A PR that adds a cell may not
edit a file the benchmark has, so the cells such PRs add are named here and
joined to that constant as the module is collected. The next ``benchmark``
PR moves them into ``test_bm_catalog.py`` and deletes this file."""

ADDED_STREAM_CELLS = {"criteo_ftrl_ps4.mesh4_stream_uniform"}      # PR 30


def pytest_collection_modifyitems(items):
    for module in {item.module for item in items
                   if getattr(item, "module", None) is not None}:
        if module.__name__ == "test_bm_catalog":
            module.STREAM_CELLS = set(module.STREAM_CELLS) | ADDED_STREAM_CELLS
