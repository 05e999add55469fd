"""Dashboard single-page UI.

Parity role: the reference's React SPA (``python/ray/dashboard/client/``,
194 TS files) — scoped to a dependency-free static page (this environment is
zero-egress: no CDN, no build step) that polls the JSON endpoints the
dashboard already serves and renders the same panes: cluster, nodes, tasks,
actors, objects, placement groups, serve, jobs, logs, event stats, stacks.
"""

PAGE = """<!DOCTYPE html>
<html>
<head>
<meta charset="utf-8">
<title>ray_tpu dashboard</title>
<style>
  :root { --bg:#10141a; --panel:#1a212b; --line:#2a3442; --fg:#d7dee8;
          --dim:#8b98a8; --acc:#4fa3ff; --ok:#38c172; --bad:#e3504f; }
  * { box-sizing:border-box; }
  body { margin:0; font:13px/1.45 ui-monospace,Consolas,monospace;
         background:var(--bg); color:var(--fg); }
  header { display:flex; align-items:center; gap:16px; padding:10px 16px;
           border-bottom:1px solid var(--line); }
  header h1 { font-size:15px; margin:0; color:var(--acc); }
  header .meta { color:var(--dim); }
  nav { display:flex; gap:2px; padding:6px 12px; border-bottom:1px solid var(--line);
        flex-wrap:wrap; }
  nav button { background:none; border:1px solid transparent; color:var(--dim);
               padding:4px 10px; cursor:pointer; font:inherit; border-radius:4px; }
  nav button.active { color:var(--fg); border-color:var(--line);
                      background:var(--panel); }
  main { padding:12px 16px; }
  table { border-collapse:collapse; width:100%; margin:8px 0 20px; }
  th, td { text-align:left; padding:4px 10px; border-bottom:1px solid var(--line);
           white-space:nowrap; overflow:hidden; text-overflow:ellipsis;
           max-width:420px; }
  th { color:var(--dim); font-weight:normal; position:sticky; top:0;
       background:var(--bg); }
  .ok { color:var(--ok); } .bad { color:var(--bad); }
  .bar { display:inline-block; height:9px; background:var(--acc);
         border-radius:2px; vertical-align:middle; }
  .barbg { display:inline-block; width:120px; height:9px; background:var(--panel);
           border-radius:2px; vertical-align:middle; margin-right:6px; }
  pre { background:var(--panel); padding:10px; border-radius:4px;
        overflow:auto; max-height:70vh; }
  h2 { font-size:13px; color:var(--dim); text-transform:uppercase;
       letter-spacing:.08em; margin:14px 0 2px; }
</style>
</head>
<body>
<header>
  <h1>ray_tpu</h1>
  <span class="meta" id="updated"></span>
  <span class="meta bad" id="err"></span>
</header>
<nav id="nav"></nav>
<main id="main">loading…</main>
<script>
const TABS = ["overview","incidents","node_stats","metrics","tasks","actors",
              "launch","decisions","objects","memory","network",
              "placement_groups","serve","jobs","train","logs","events",
              "event_stats","traces","latency","stacks","profile"];
// hash may carry a selection suffix, e.g. "#traces:<trace_id>"
let tab = (location.hash.slice(1) || "overview").split(":")[0] || "overview";
window.addEventListener("hashchange", () => {
  tab = (location.hash.slice(1) || "overview").split(":")[0] || "overview";
  nav();
});
const $ = (id) => document.getElementById(id);

function nav() {
  $("nav").innerHTML = TABS.map(t =>
    `<button class="${t===tab?'active':''}" onclick="go('${t}')">${t}</button>`
  ).join("");
}
function go(t) { tab = t; location.hash = t; nav(); refresh(); }

async function j(path) {
  const r = await fetch(path);
  if (!r.ok) throw new Error(path + " -> " + r.status);
  return r.json();
}
function esc(s) { return String(s).replace(/&/g,"&amp;").replace(/</g,"&lt;"); }
function table(rows, cols) {
  if (!rows || !rows.length) return "<p class='meta'>none</p>";
  cols = cols || Object.keys(rows[0]);
  return "<table><tr>" + cols.map(c=>`<th>${c}</th>`).join("") + "</tr>" +
    rows.map(r => "<tr>" + cols.map(c => {
      let v = r[c];
      if (v !== null && typeof v === "object") v = JSON.stringify(v);
      let cls = "";
      if (c === "state" || c === "status" || c === "alive")
        cls = /ALIVE|FINISHED|RUNNING|true|SUCCEEDED|HEALTHY|^INFO$/i.test(String(v)) ? "ok"
            : /DEAD|FAILED|false|UNHEALTHY|ERROR/i.test(String(v)) ? "bad" : "";
      return `<td class="${cls}">${esc(v===undefined?"":v)}</td>`;
    }).join("") + "</tr>").join("") + "</table>";
}
function bars(total, avail) {
  return "<table>" + Object.keys(total).sort().map(k => {
    const used = total[k] - (avail[k] || 0);
    const pct = total[k] ? Math.round(100*used/total[k]) : 0;
    return `<tr><td>${esc(k)}</td>
      <td><span class="barbg"><span class="bar" style="width:${Math.round(pct*1.2)}px"></span></span>
      ${used.toFixed(1)} / ${total[k].toFixed(1)} used</td></tr>`;
  }).join("") + "</table>";
}

const RENDER = {
  async overview() {
    const s = await j("/api/cluster_status");
    const nodes = s.nodes || [];
    return "<h2>resources</h2>" + bars(s.total || {}, s.available || {}) +
      `<h2>nodes (${nodes.length})</h2>` +
      table(nodes, ["node_id","alive","total","available","labels"]);
  },
  async tasks() {
    const rows = await j("/api/tasks");
    const by = {};
    rows.forEach(r => { by[r.state] = (by[r.state]||0)+1; });
    return "<h2>by state</h2><p>" +
      Object.entries(by).map(([k,v])=>`${k}: ${v}`).join(" · ") + "</p>" +
      "<h2>latest</h2>" + table(rows.slice(-200).reverse());
  },
  async actors() { return table(await j("/api/actors")); },
  async launch() {
    // control plane: actor-launch lifecycle profile — per-stage
    // latency stats over recent creations + the recent-launch ring
    const p = await j("/api/launch?limit=30");
    const head = `<p>${p.launched_total||0} launches total · ` +
      `${p.window||0} in window` +
      (p.total && p.total.count ?
        ` · total mean ${p.total.mean_ms}ms p95 ${p.total.p95_ms}ms` : "") +
      `</p>`;
    const stages = table(Object.entries(p.stages||{}).map(([k,v]) => ({
      stage: k.replace("_ms",""), count: v.count, "mean ms": v.mean_ms,
      "p50 ms": v.p50_ms, "p95 ms": v.p95_ms, "max ms": v.max_ms,
    })));
    const boot = Object.entries(p.worker_boot_stage_seconds||{})
      .map(([k,v])=>`${k.replace("_ms","")}=${v}s`).join(" · ");
    const recent = table((p.recent||[]).slice().reverse().map(r => ({
      actor: (r.actor||"").slice(0,14), name: r.name||"",
      node: (r.node||"").slice(0,8),
      stages: Object.entries(r.stages||{})
        .filter(([k])=>k!=="total_ms")
        .map(([k,v])=>`${k.replace("_ms","")}=${v}`).join(" "),
      "total ms": (r.stages||{}).total_ms,
      trace: r.trace || "",
    })));
    return head + "<h2>stage latency</h2>" + stages +
      (boot ? `<h2>worker boot (cumulative s)</h2><p>${boot}</p>` : "") +
      "<h2>recent launches</h2>" + recent;
  },
  async decisions() {
    // decision flight recorder: placement + autoscaler rows, newest first
    const rows = await j("/api/decisions?limit=200");
    const by = {};
    rows.forEach(r => { by[r.kind] = (by[r.kind]||0)+1; });
    const shaped = rows.slice().reverse().map(r => ({
      seq: r.seq, kind: r.kind,
      detail: Object.entries(r).filter(([k]) =>
        !["seq","t","kind"].includes(k))
        .map(([k,v]) => `${k}=${v!==null&&typeof v==="object"?JSON.stringify(v):v}`)
        .join(" "),
    }));
    return "<h2>by kind</h2><p>" +
      Object.entries(by).map(([k,v])=>`${k}: ${v}`).join(" · ") + "</p>" +
      "<h2>decisions (newest first)</h2>" +
      table(shaped, ["seq","kind","detail"]);
  },
  async objects() {
    const rows = await j("/api/objects");
    const total = rows.reduce((a,r)=>a+(r.size_bytes||0), 0);
    return `<p>${rows.length} objects, ${(total/1e6).toFixed(1)} MB</p>` +
      table(rows.slice(0,300));
  },
  async memory() {
    // memory plane: live objects grouped by creation callsite, store
    // usage split (sealed vs unsealed vs capacity), leak suspects
    const s = await j("/api/memory?group_by=callsite&limit=50");
    const st = s.store || {};
    const mb = (n)=> ((n||0)/1e6).toFixed(1);
    const rows = (s.rows||[]).map(g => ({
      callsite: g.group, count: g.count, mb: mb(g.bytes),
      leak: g.leak_suspect ? "YES" : "",
      classes: Object.entries(g.classes||{}).map(([c,n])=>`${c}:${n}`).join(" "),
      jobs: (g.jobs||[]).join(" "),
      exemplars: (g.exemplars||[]).map(o=>o.slice(0,12)).join(" "),
    }));
    const leaks = Object.values(s.leak_suspects||{}).map(i => ({
      callsite: i.callsite, live: i.live_count, mb: mb(i.live_bytes),
      growth_mb: mb(i.growth_bytes), window_s: i.window_s,
    }));
    return `<p>${s.total_objects} live objects, ${mb(s.total_bytes)} MB — ` +
      `store sealed ${mb(st.sealed_bytes)} / unsealed ${mb(st.unsealed_bytes)} ` +
      `/ capacity ${mb(st.capacity_bytes)} / high-water ${mb(st.highwater_bytes)} MB</p>` +
      (leaks.length ? `<h2>leak suspects</h2>` +
        table(leaks, ["callsite","live","mb","growth_mb","window_s"]) : "") +
      `<h2>by creation callsite</h2>` +
      table(rows, ["callsite","count","mb","leak","classes","jobs","exemplars"]);
  },
  async network() {
    // transfer plane: per-link ledger matrix, relay topology (recent
    // transfers grouped by object, hop-indented), fleet path summary
    const s = await j("/api/net");
    const mb = (n)=> ((n||0)/1e6).toFixed(1);
    const sum = s.summary || {};
    const head = `<p>${sum.inflight||0} in flight · ` +
      `${sum.retries||0} retries · ${sum.stalled||0} stalls · ` +
      `${sum.leaked_buffers||0} leaked buffers (${mb(sum.leaked_bytes)} MB) · ` +
      `${sum.slow_link_events||0} slow-link events</p>`;
    const paths = table((sum.rows||[]).map(r => ({
      path: r.group, mb: mb(r.bytes), transfers: r.transfers,
      "GiB/s": r.gib_per_s == null ? "" : r.gib_per_s,
      failures: r.failures, stalls: r.stalls,
    })));
    const links = table((s.links||[]).map(r => ({
      state: r.slow ? "SLOW" : "ok", src: r.src, dst: r.dst, path: r.path,
      mb: mb(r.bytes), xfers: r.transfers, fail: r.failures,
      stall: r.stalls, infl: r.inflight,
      "GiB/s": r.ewma_gib_per_s == null ? "" : r.ewma_gib_per_s,
      hop: r.max_hop,
    })), ["state","src","dst","path","mb","xfers","fail","stall","infl",
          "GiB/s","hop"]);
    // relay topology: recent transfers of one object rendered as a tree
    // of hops (hop 0 = pull off the sealed origin)
    const byObj = {};
    (s.transfers||[]).forEach(t => {
      (byObj[t.object_id] = byObj[t.object_id] || []).push(t);
    });
    const relays = Object.entries(byObj)
      .filter(([,ts]) => ts.length > 1 || ts.some(t => t.hop > 0))
      .slice(0, 8).map(([oid, ts]) =>
        `<h2>object ${esc(oid.slice(0,16))} — relay tree</h2>` +
        ts.sort((a,b)=>(a.hop-b.hop)).map(t =>
          `<div style="margin-left:${(t.hop||0)*18}px">` +
          `hop ${t.hop||0}: ${esc(t.src)} → ${esc(t.dst)} ` +
          `<span class="meta">${t.path} ${mb(t.bytes)} MB` +
          `${t.gib_per_s != null ? " @ " + t.gib_per_s + " GiB/s" : ""}` +
          `${t.ok ? "" : " FAILED"}</span></div>`).join("")
      ).join("");
    const recent = table((s.transfers||[]).slice(0, 30).map(t => ({
      state: t.ok ? "ok" : "FAILED", object: t.object_id.slice(0,14),
      link: `${t.src}→${t.dst}`, path: t.path, hop: t.hop,
      mb: mb(t.bytes), "GiB/s": t.gib_per_s == null ? "" : t.gib_per_s,
      stages: Object.entries(t.stages_ms||{})
        .map(([k,v])=>`${k.replace("_ms","")}=${v}`).join(" "),
      trace: t.trace_id || "",
    })), ["state","object","link","path","hop","mb","GiB/s","stages","trace"]);
    return head + "<h2>by path</h2>" + paths +
      "<h2>link matrix</h2>" + links + relays +
      "<h2>recent transfers</h2>" + recent;
  },
  async placement_groups() { return table(await j("/api/placement_groups")); },
  async serve() {
    const s = await j("/api/serve");
    return "<pre>" + esc(JSON.stringify(s, null, 2)) + "</pre>";
  },
  async jobs() {
    // multi-tenant job plane: arbitration rows (priority / quota / live
    // usage / admission + queue position) over every job the scheduler
    // has seen, then the JobSubmissionClient's submission records
    const s = await j("/api/jobs");
    const jobs = (s.jobs || []).map(r => ({
      name: r.name, status: r.admission,
      "q#": r.queue_position || "",
      prio: r.priority, weight: r.weight,
      running: r.running, ready: r.ready,
      usage: r.usage, quota: r.quota,
      "obj MB": ((r.object_store_bytes||0)/1e6).toFixed(1),
      preempt: r.preemptions, oom: r.oom_kills,
    }));
    const subs = s.submissions || [];
    return `<h2>arbitration (${jobs.length})</h2>` +
      table(jobs, ["name","status","q#","prio","weight","running","ready",
                   "usage","quota","obj MB","preempt","oom"]) +
      `<h2>submissions (${subs.length})</h2>` + table(subs);
  },
  async train() {
    // training step plane: run digests; ?run drills into the per-rank
    // step waterfall (stage-colored bars) + downtime ledger
    const STAGES = ["data_wait_ms","host_to_device_ms","compile_ms",
                    "compute_ms","collective_wait_ms","checkpoint_stall_ms",
                    "report_ms","other_ms"];
    const COLORS = {data_wait_ms:"#e3a04f", host_to_device_ms:"#b06fd8",
                    compile_ms:"#e3504f", compute_ms:"#38c172",
                    collective_wait_ms:"#4fa3ff",
                    checkpoint_stall_ms:"#d8c94f", report_ms:"#4fd8c9",
                    other_ms:"#6b7a8c"};
    const sel = location.hash.split(":")[1];
    if (sel) {
      const d = await j("/api/train?run=" + sel);
      if (!d.run) return `<p>no step records for run ${esc(sel)}</p>`;
      const meta = d.meta || {}, gp = meta.goodput || {};
      const ledger = meta.downtime_ledger || [];
      const legend = STAGES.map(s =>
        `<span style="color:${COLORS[s]}">■ ${s.replace("_ms","")}</span>`
      ).join(" ");
      const bar = (st, wall) => {
        if (!wall) return "";
        return `<span class="barbg" style="width:240px">` + STAGES.map(k => {
          const w = Math.round(240 * (st[k]||0) / wall);
          return w ? `<span class="bar" style="width:${w}px;background:${COLORS[k]}"></span>` : "";
        }).join("") + `</span>`;
      };
      const rows = [];
      (d.steps || []).slice(-50).forEach(s => {
        const skew = (d.skew || {})[s.step] || {};
        Object.keys(s.ranks || {}).sort().forEach(r => {
          const rec = s.ranks[r], st = rec.stages || {};
          rows.push(`<tr><td>${s.step}</td><td>${r}` +
            `${skew.straggler_rank == r && skew.skew_ms > 0 ? " ⚠" : ""}</td>` +
            `<td>${bar(st, rec.wall_ms)}</td>` +
            `<td>${(rec.wall_ms||0).toFixed(1)}ms</td>` +
            `<td>${rec.recompiled ? "<span class='bad'>RECOMPILED</span>" : ""}` +
            `${rec.trace_id ? ` <a href="#traces:${rec.trace_id}">trace</a>` : ""}</td></tr>`);
        });
      });
      return `<h2>run ${esc(d.run)} — world ${d.world}, ` +
        `${d.steps_seen} steps, ${d.recompiles} recompiles` +
        `${gp.goodput != null ? `, goodput ${gp.goodput.toFixed(3)}` : ""}</h2>` +
        `<p>${legend}</p>` +
        (ledger.length ? "<h2>downtime ledger</h2>" +
          table(ledger.map(e => ({cause: e.cause,
            seconds: (e.seconds||0).toFixed(2), detail: e.detail||""}))) : "") +
        "<h2>step waterfall (per rank)</h2>" +
        "<table><tr><th>step</th><th>rank</th><th>stages</th><th>wall</th>" +
        "<th></th></tr>" + rows.join("") + "</table>";
    }
    const rows = await j("/api/train");
    if (!rows.length) return "<p>no training runs recorded</p>";
    const cols = ["run","world","steps","recompiles","goodput","downtime s",
                  "data wait","skew ms","status"];
    return "<h2>training runs (click to inspect)</h2>" +
      "<table><tr>" + cols.map(c=>`<th>${c}</th>`).join("") + "</tr>" +
      rows.map(r =>
        `<tr><td><a href="#train:${encodeURIComponent(r.run)}" ` +
        `onclick="setTimeout(refresh,0)">${esc(r.run)}</a></td>` +
        `<td>${r.world}</td><td>${r.steps}</td><td>${r.recompiles}</td>` +
        `<td>${r.goodput == null ? "" : r.goodput.toFixed(3)}</td>` +
        `<td>${(r.downtime_s||0).toFixed(1)}</td>` +
        `<td>${r.data_wait_ratio == null ? "" :
               (100*r.data_wait_ratio).toFixed(1) + "%"}</td>` +
        `<td>${(r.max_skew_ms||0).toFixed(1)}</td>` +
        `<td class="${/finished/.test(r.status)?'ok':/failed/.test(r.status)?'bad':''}">${esc(r.status)}</td></tr>`
      ).join("") + "</table>";
  },
  async logs() { return table(await j("/api/logs")); },
  async incidents() {
    // alerting plane: open/closed incidents + registered SLO burn status;
    // "#incidents:<id>" drills into one record's cross-plane digest
    const sel = (location.hash.slice(1).split(":")[1] || "");
    if (sel) {
      const inc = await j("/api/incidents?id=" + encodeURIComponent(sel));
      if (!inc) return "<p class='meta'>no such incident</p>";
      const d = inc.digest || {};
      let html = `<h2>${esc(inc.id)} [${esc(inc.kind)}] ` +
        `${esc(inc.subject)}</h2>` +
        `<p>state=${esc(inc.state)} severity=${esc(inc.severity)} ` +
        `triggers=${inc.count}` +
        (inc.duration_s != null ? ` duration=${inc.duration_s}s` : "") +
        `</p>` +
        (inc.verdict ? `<p><b>verdict:</b> ${esc(inc.verdict)}</p>` : "") +
        `<p>planes joined: ${esc((d.planes||[]).join(", "))}</p>`;
      if (d.traces && d.traces.length)
        html += "<h2>exemplar traces</h2>" + table(d.traces);
      if (d.net && d.net.links && d.net.links.length)
        html += "<h2>link ledger</h2>" + table(d.net.links,
          ["src","dst","path","ewma_gib_per_s","stalls","failures","slow"]);
      if (d.memory && d.memory.top_callsites)
        html += "<h2>memory top callsites</h2>" +
          table(d.memory.top_callsites);
      if (d.train) html += "<h2>train run</h2>" + table([d.train]);
      if (d.control && d.control.launches)
        html += "<h2>recent launches</h2>" + table(d.control.launches);
      if (d.events && d.events.length)
        html += "<h2>correlated events</h2>" +
          table(d.events.slice(-30).reverse(),
                ["time","severity","type","source","message"]);
      return html + `<p><a href="#incidents" onclick="go('incidents')">` +
        `back to incident list</a></p>`;
    }
    const body = await j("/api/incidents?limit=100");
    const incRows = (body.incidents || []).map(r => ({
      id: `<a href="#incidents:${esc(r.id)}" ` +
          `onclick="location.hash='incidents:${esc(r.id)}';refresh()">` +
          `${esc(r.id)}</a>`,
      state: r.state, kind: r.kind, subject: r.subject,
      triggers: r.count,
      duration_s: r.duration_s != null ? r.duration_s : "open",
      planes: (r.planes || []).join(","),
      verdict: r.verdict || "",
    }));
    const sloRows = (body.slos || []).map(s => ({
      name: s.name, kind: s.kind, target: s.target,
      state: s.ok ? "OK" : "BREACHED",
      subjects: s.subjects, breaches: s.breaches_total,
      worst: s.worst ? JSON.stringify(s.worst) : "",
    }));
    // id cells carry markup: render with a raw table to keep the links
    const raw = (rows, cols) => !rows.length ? "<p class='meta'>none</p>" :
      "<table><tr>" + cols.map(c=>`<th>${c}</th>`).join("") + "</tr>" +
      rows.map(r => "<tr>" + cols.map(c => {
        const cls = (c === "state")
          ? (/open|BREACHED/.test(String(r[c])) ? "bad" : "ok") : "";
        return `<td class="${cls}">${c==="id" ? r[c] : esc(r[c]??"")}</td>`;
      }).join("") + "</tr>").join("") + "</table>";
    return `<h2>incidents (${incRows.length})</h2>` +
      raw(incRows, ["id","state","kind","subject","triggers","duration_s",
                    "planes","verdict"]) +
      `<h2>SLOs (${sloRows.length})</h2>` +
      raw(sloRows, ["name","state","kind","target","subjects","breaches",
                    "worst"]);
  },
  async events() {
    // cluster event log (failure forensics): newest first, severity colored
    const rows = await j("/api/events?limit=500");
    const by = {};
    rows.forEach(r => { by[r.severity] = (by[r.severity]||0)+1; });
    const cols = ["event_id","state","type","source","message","task_id",
                  "node_id","pid"];
    const shaped = rows.slice().reverse().map(r => {
      const o = {};
      cols.forEach(c => { o[c] = r[c]; });
      o.state = r.severity;  // severity under the colorized "state" column
      return o;
    });
    return "<h2>by severity</h2><p>" +
      Object.entries(by).map(([k,v])=>`${k}: ${v}`).join(" · ") + "</p>" +
      "<h2>latest</h2>" + table(shaped, cols);
  },
  async event_stats() {
    const s = await j("/api/event_stats");
    return "<pre>" + esc(JSON.stringify(s, null, 2)) + "</pre>";
  },
  async metrics() {
    // runtime-internal series (telemetry plane); /metrics has the same
    // data in Prometheus text for scrapers
    const series = await j("/api/runtime_metrics");
    return series.map(s => {
      const rows = Object.entries(s.data || {}).map(([labels, v]) =>
        ({labels, value: v}));
      return `<h2>${esc(s.name)} <span class="meta">(${esc(s.kind)})</span></h2>` +
        `<p class="meta">${esc(s.description || "")}</p>` + table(rows);
    }).join("");
  },
  async stacks() {
    const s = await j("/api/stacks");
    return Object.entries(s).map(([proc, txt]) =>
      `<h2>${esc(proc)}</h2><pre>${esc(txt)}</pre>`).join("");
  },
  async traces() {
    // request-tracing plane: recent traces; ?id= drills into one span tree
    const sel = location.hash.split(":")[1];
    if (sel) {
      const t = await j("/api/trace?id=" + sel);
      const render = (s, depth) => {
        const bd = Object.entries(s.breakdown||{})
          .map(([k,v]) => `${k.replace("_ms","")}=${v}ms`).join(" ");
        return `<div style="margin-left:${depth*18}px">` +
          `<b>${esc(s.name||s.span_id.slice(0,8))}</b> ` +
          `${(s.duration_ms||0).toFixed(1)}ms ` +
          `<span class="meta">${esc(bd)}</span></div>` +
          (s.children||[]).map(c => render(c, depth+1)).join("");
      };
      return `<h2>trace ${esc(t.trace_id)} — ` +
        `${(t.duration_ms||0).toFixed(1)}ms, ${t.spans} spans</h2>` +
        (t.tree||[]).map(r => render(r, 0)).join("") +
        "<h2>critical path</h2>" +
        table((t.critical_path||[]).map(r => ({
          name: r.name, "ms": (r.duration_ms||0).toFixed(1),
          breakdown: Object.entries(r.breakdown||{})
            .map(([k,v]) => `${k.replace("_ms","")}=${v}`).join(" "),
        })));
    }
    const rows = await j("/api/traces?limit=100");
    if (!rows.length) return "<p>no traces recorded yet</p>";
    return "<h2>recent traces (click to inspect)</h2>" +
      rows.map(r =>
        `<div><a href="#traces:${r.trace_id}" onclick="setTimeout(refresh,0)">` +
        `${r.trace_id}</a> <b>${esc(r.root||"")}</b> ` +
        `<span class="meta">${r.events} events, ` +
        `${r.last_time ? ((Date.now()/1000)-r.last_time).toFixed(1) : "?"}s ago` +
        `</span></div>`).join("");
  },
  async latency() {
    // sliding-window p50/p95/p99 per job with exemplar trace links
    const s = await j("/api/job_latency");
    return Object.entries(s).map(([job, w]) =>
      `<h2>job ${esc(job)} <span class="meta">(${w.count} in window)</span></h2>` +
      table([{p50: w.p50, p95: w.p95, p99: w.p99, max: w.max}]) +
      (w.exemplars||[]).map(e =>
        `<p class="meta">slow: ${e.latency_ms}ms — ` +
        `<a href="#traces:${e.trace_id}" onclick="tab='traces';nav();setTimeout(refresh,0)">${e.trace_id}</a></p>`
      ).join("")
    ).join("") || "<p>no samples in window</p>";
  },
  async node_stats() {
    // per-node reporter metrics (cpu/mem/object-store), heartbeat-pushed
    const s = await j("/api/node_stats");
    const rows = Object.entries(s).map(([nid, st]) => ({
      node: st.node || nid.slice(0,12),
      "cpu %": st.cpu_percent,
      "rss MB": st.rss_bytes ? (st.rss_bytes/1e6).toFixed(1) : "",
      "store MB": st.object_store_bytes ? (st.object_store_bytes/1e6).toFixed(1) : "0.0",
      "mem avail GB": st.mem_available ? (st.mem_available/1e9).toFixed(2) : "",
      workers: st.workers,
      "lease q/run": (st.lease_queued??"") + "/" + (st.lease_running??""),
      "hb age s": st.heartbeat_age_s ?? 0,
    }));
    return table(rows);
  },
  async profile() {
    // py-spy-style sampled stacks across node daemons (2s capture)
    $("main").innerHTML = "sampling node stacks for 2s\u2026";
    const s = await j("/api/profile?duration=2");
    return Object.entries(s).map(([node, counts]) => {
      const total = Object.values(counts).reduce((a,b)=>a+b, 0) || 1;
      const lines = Object.entries(counts).slice(0, 40).map(([stack, n]) =>
        `${String(Math.round(100*n/total)).padStart(3)}%  ${esc(stack)}`);
      return `<h2>${esc(node)}</h2><pre>${lines.join("\n")}</pre>`;
    }).join("");
  },
};

let timer = null;
async function refresh() {
  try {
    $("main").innerHTML = await RENDER[tab]();
    $("updated").textContent = "updated " + new Date().toLocaleTimeString();
    $("err").textContent = "";
  } catch (e) {
    $("err").textContent = String(e);
  }
  clearTimeout(timer);
  timer = setTimeout(refresh, (tab === "stacks" || tab === "profile") ? 15000 : 2000);
}
nav();
refresh();
</script>
</body>
</html>
"""
