"""The access-profile candidates of the search cell, kept with the benchmark.

A copy of the candidate builder and of the realization of an assignment in
``repro.core.scheduler`` (``profile_candidates``, ``realized_campaign``),
so that the campaign a sampled member is checked on cannot move when the
program's builder changes. It builds the program's public input types.

Each access of the campaign gets three candidates, in this order, reading
the same replica released at the same tick:

0. remote access (WebDAV) over the replica's SE -> worker-node link (the
   threads of one job share one stream);
1. stage-in (xrdcp) over that same link;
2. placement (gsiftp) to the local SE, then stage-in over the local SE ->
   worker-node link; an access whose replica already sits there has no
   placement.

An assignment picks candidate ``assign[i]`` (modulo its candidates) of
access ``i``; the
realized campaign holds, job by job and in access order, the picked
candidates, on the same grid (so the same links in the same order).
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

from repro.core.topology import Grid
from repro.core.workload import AccessProfileKind, Campaign, FileAccess, Job, Replica

#: (job index, (remote, stage-in[, placement])) per access
Candidates = List[Tuple[int, Tuple[FileAccess, ...]]]
#: the protocols of the three profiles (the paper's Sec. 3 and Sec. 5)
REMOTE_PROTOCOL = "webdav"
STAGEIN_PROTOCOL = "xrdcp"
PLACEMENT_PROTOCOL = "gsiftp"


def add_links(grid: Grid, links: Sequence[dict]) -> Grid:
    """``grid`` with the configuration's extra links added."""
    for link in links:
        grid.add_link(link["src"], link["dst"], bandwidth=float(link["bandwidth"]),
                      bg_update_period=int(link["bg_update_period"]))
    return grid


def candidates(campaign: Campaign, local_se: str) -> Candidates:
    out: Candidates = []
    for j, job in enumerate(campaign.jobs):
        for acc in job.accesses:
            rep = Replica(acc.replica.size_mb, acc.replica.storage_element)
            t0 = acc.release_tick
            options = [FileAccess(rep, AccessProfileKind.REMOTE, REMOTE_PROTOCOL, t0),
                       FileAccess(rep, AccessProfileKind.STAGE_IN, STAGEIN_PROTOCOL, t0)]
            if rep.storage_element != local_se:
                options.append(FileAccess(
                    rep, AccessProfileKind.DATA_PLACEMENT, PLACEMENT_PROTOCOL, t0,
                    local_storage_element=local_se, stagein_protocol=STAGEIN_PROTOCOL))
            out.append((j, tuple(options)))
    return out


def realized(campaign: Campaign, cands: Candidates, assign) -> Campaign:
    """The campaign one assignment stands for: job ``j`` keeps its worker
    node and makes the picked candidates of its accesses, in order."""
    jobs: List[List[FileAccess]] = [[] for _ in campaign.jobs]
    for (j, options), k in zip(cands, assign):
        jobs[j].append(options[int(k) % len(options)])
    return Campaign(tuple(Job(job.worker_node, tuple(a), name=f"job{j}")
                          for j, (job, a) in enumerate(zip(campaign.jobs, jobs))),
                    name="realized")
