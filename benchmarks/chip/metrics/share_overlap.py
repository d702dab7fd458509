"""Share runner: how many shares run at once. Per request, the seconds of
its shares' ``runner.share`` spans summed, over the request's ``run_s``;
mean over the window's requests. About 1 where shares run one after
another, about N where N chips serve a request's shares at the same time.
None where the program records no spans."""


def read(ctx):
    ratios = []
    for r in ctx.records:
        shares = [sp.seconds for s in r.shares
                  for sp in getattr(s, "spans", ()) if sp.name == "runner.share"]
        if shares and r.run_s > 0:
            ratios.append(sum(shares) / r.run_s)
    return sum(ratios) / len(ratios) if ratios else None
