"""Builds the system under test for a configuration: a namenode cluster
over the columnar store, with the configuration's namespace bulk-loaded.

Loading follows the program's ``materialize_big_dir``
(src/repro/core/namenode.py, lines 1361-1381): rows are direct table
puts, ids come from the first namenode's allocator, and no transaction
runs -- so 10^6 inodes load in seconds and every run starts from the
same store.
"""
from __future__ import annotations

from nsplan import NamespacePlan


def build_cluster(config: dict, plan: NamespacePlan):
    """(store, cluster) loaded with ``plan``, per ``config["cluster"]``."""
    from repro.core import NamenodeCluster, format_fs
    from repro.core.columnar import ColumnarMetadataStore
    from repro.core.tables import ROOT_ID, make_inode

    c = config["cluster"]
    store = ColumnarMetadataStore(n_datanodes=c["datanodes"],
                                  replication=c["replication"],
                                  n_partitions=c["partitions"])
    format_fs(store)
    cluster = NamenodeCluster(store, c["namenodes"])
    ids = cluster.namenodes[0].ops.inode_ids
    put = store.table("inode").put

    for part in plan.trees:
        file_names = [part.file_name(i) for i in range(part.files_per_dir)]
        for t in range(part.trees):
            # heap order: dir j's parent is (j - 1) // dirs_per_dir
            dir_ids = []
            for j, path in enumerate(plan.tree_dirs(part, t)):
                parent = (ROOT_ID if j == 0
                          else dir_ids[(j - 1) // part.dirs_per_dir])
                did = ids.next_id()
                dir_ids.append(did)
                put(make_inode(did, parent, path.rsplit("/", 1)[1], True))
                for name in file_names:
                    put(make_inode(ids.next_id(), did, name, False))
    return store, cluster
