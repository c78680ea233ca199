"""Web-status dashboard (ref: veles/web_status.py:113-314 + the node.js
frontend in web/).

The reference ran a Tornado server fed by POSTs from masters, with MongoDB
log browsing.  Here a stdlib HTTP server serves: ``/`` (HTML dashboard),
``/api/status`` (registered workflow metrics), ``/api/events`` (the
structured trace ring buffer from veles_tpu.logger), ``/api/plots`` (the
PlotBus payloads), and accepts POST ``/update`` from remote runs — same
capability surface, no external deps."""

import json
import math
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from veles_tpu import telemetry
from veles_tpu.logger import Logger, events
from veles_tpu.services.plotting import bus

_PAGE = """<!DOCTYPE html>
<html><head><title>veles_tpu status</title>
<style>body{font-family:monospace;margin:2em}table{border-collapse:collapse}
td,th{border:1px solid #999;padding:4px 8px}
.spark{display:inline-block;margin:0 1.5em .8em 0}
.spark svg{vertical-align:middle;background:#f6f6f6}
.spark .v{color:#06c}
#graph svg,#timeline svg{background:#fafafa;border:1px solid #ddd}
.node{font-size:11px}.lane{font-size:10px;fill:#555}</style></head>
<body><h2>veles_tpu status <span id="health"></span></h2>
<div id="status"></div><h3>metrics</h3><div id="metrics"></div>
<h3>telemetry <small>(process metrics registry —
<a href="/metrics">prometheus</a> ·
<a href="/api/telemetry">json</a>)</small></h3>
<div id="mfu"></div><div id="telemetry"></div>
<h3>serving <small>(ContinuousEngine slot pool: queue depth,
p50/p99 queue-wait and per-stream decode rate)</small></h3>
<div id="serving">(no serving endpoint registered)</div>
<h3>workflow graph <small>(nodes heat-colored by run-time share;
<a href="/api/dot">DOT</a>)</small></h3><div id="graph"></div>
<h3>event timeline <small>(<a href="/api/trace">chrome trace</a> —
load in Perfetto / chrome://tracing)</small></h3>
<div id="timeline"></div>
<h3>device profiler <small>(jax.profiler window over the live process;
<a href="/api/profile/trace">latest trace</a> — load in
Perfetto)</small></h3>
<div><button onclick="capProf()">capture 3s</button>
<span id="prof"></span></div>
<script>
async function capProf(){
 const r=await (await fetch('/api/profile',{method:'POST',
  body:JSON.stringify({seconds:3})})).json();
 document.getElementById('prof').textContent=JSON.stringify(r);
 setTimeout(async()=>{
  const s=await (await fetch('/api/profile')).json();
  document.getElementById('prof').textContent=JSON.stringify(s);},4000);
}
</script>
<h3>bench <small>(newest ledger rows vs the roofline model's
prediction — <a href="/api/bench">json</a>)</small></h3>
<div id="bench"></div>
<script>
(async function(){
 try{
  const b=await (await fetch('/api/bench')).json();
  const m=b.measured||{}, p=b.predicted||{};
  const keys=['value','gemm_bf16_gflops','lm_large_tokens_per_sec',
   'lm_large_mfu','lm_tokens_per_sec','alexnet_samples_per_sec',
   'flash_ms_long_t8192','serve_ms_per_tok_int8','mlp_step_fused_ms',
   'beam_ms_per_pos_t4096'];
  let h='<table border=0 cellpadding=3><tr><th align=left>metric'+
   '</th><th>measured</th><th>predicted</th><th>ratio</th></tr>';
  for(const k of keys){
   const mv=m[k], pv=p[k];
   if(mv==null&&pv==null)continue;
   const r=(mv&&pv)?(mv/pv).toFixed(2):'';
   h+='<tr><td>'+k+'</td><td align=right>'+(mv??'')+
    '</td><td align=right>'+(pv??'')+'</td><td align=right>'+r+
    '</td></tr>';
  }
  h+='</table><small>measured_at '+(b.measured_at||'never')+
   '</small>';
  document.getElementById('bench').innerHTML=h;
 }catch(e){document.getElementById('bench').textContent=String(e);}
})();
</script>
<h3>perf ledger <small>(persistent per-key history + regression
sentinel — <a href="/api/perf">json</a>; drift rides
<a href="/metrics">/metrics</a> as veles_perf_drift /
veles_perf_regressions_total)</small></h3>
<div id="perf"></div>
<script>
(async function(){
 try{
  const p=await (await fetch('/api/perf')).json();
  const ks=p.keys||[];
  if(!ks.length){document.getElementById('perf').textContent=
   '(empty ledger: '+(p.ledger||p.error||'?')+')';return;}
  let h='<table><tr><th align=left>key</th><th>trend</th>'+
   '<th>last</th><th>median</th><th>drift</th><th>target</th>'+
   '<th>verdict</th></tr>';
  for(const k of ks.slice(0,40)){
   const v=k.verdict||{};
   const pts=(k.trend||[]).map((y,i)=>[i,y]);
   const badge=v.status==='regression'?
    '<b style="color:#c00">regression</b>':
    v.status==='improved'?'<b style="color:#2a2">improved</b>':
    esc(v.status||'?');
   h+='<tr><td>'+esc(k.key)+'</td><td>'+
    (pts.length>1?sparkline(pts):'')+'</td><td align=right>'+
    esc(k.last??'')+'</td><td align=right>'+
    (v.median==null?'':Number(v.median).toPrecision(4))+
    '</td><td align=right>'+
    (v.drift==null?'':(100*v.drift).toFixed(1)+'%')+
    '</td><td align=right>'+esc(v.target??'')+'</td><td>'+badge+
    (v.target_met===false?
     ' <b style="color:#c60">target missed</b>':'')+'</td></tr>';
  }
  document.getElementById('perf').innerHTML=h+'</table>';
 }catch(e){document.getElementById('perf').textContent=String(e);}
})();
</script>
<h3>recent events</h3><div id="events"></div>
<h3>log browser <small>(cross-run, needs --log-db)</small></h3>
<div><input id="logq" placeholder="substring" size="24">
<select id="logrun"><option value="">all runs</option></select>
<button onclick="searchLogs()">search</button></div>
<div id="logs"></div>
<script>
async function loadRuns(){
 try{
  const r=await (await fetch('/api/logruns')).json();
  const sel=document.getElementById('logrun');
  (r.runs||[]).forEach(x=>{const o=document.createElement('option');
   o.value=x.session; o.textContent=x.session+' ('+x.records+')';
   sel.appendChild(o);});
 }catch(e){}
}
function esc(s){return String(s).replace(/&/g,'&amp;')
 .replace(/</g,'&lt;').replace(/>/g,'&gt;');}
async function searchLogs(){
 const q=encodeURIComponent(document.getElementById('logq').value);
 const s=encodeURIComponent(document.getElementById('logrun').value);
 const r=await (await fetch('/api/logs?q='+q+'&session='+s)).json();
 // esc(): log messages are data, never markup — a logged string
 // containing tags must render inert, not execute (stored-XSS guard)
 document.getElementById('logs').innerHTML = r.error ?
  '<i>'+esc(r.error)+'</i>' :
  '<pre>'+(r.logs||[]).map(x=>esc(new Date(x.ts*1000).toISOString()+' '+
   x.session+' '+x.level[0]+' '+x.logger+': '+x.message)).join('\\n')+
  '</pre>';
}
loadRuns();
</script>
<script>
function sparkSpan(k,pts){  // shared spark markup (metrics + serving)
 return '<span class="spark">'+esc(k)+' '+sparkline(pts)+
  ' <span class="v">'+pts[pts.length-1][1].toPrecision(4)+
  '</span></span>';
}
function sparkline(points){           // [[epoch, value], ...] -> SVG
 const w=120, h=28, vals=points.map(p=>p[1]);
 const lo=Math.min(...vals), hi=Math.max(...vals), span=(hi-lo)||1;
 const xs=points.map((p,i)=>[
  i*(w-2)/Math.max(points.length-1,1)+1,
  h-2-(p[1]-lo)*(h-4)/span]);
 return '<svg width="'+w+'" height="'+h+'"><polyline fill="none" '+
  'stroke="#06c" stroke-width="1.5" points="'+
  xs.map(q=>q[0].toFixed(1)+','+q[1].toFixed(1)).join(' ')+'"/></svg>';
}
function layers(g){   // longest-path-ish layering; repeater back-edges
 const n=g.nodes.length, adj=Array.from({length:n},()=>[]);   // ignored
 const indeg=new Array(n).fill(0);
 g.edges.forEach(([a,b])=>{adj[a].push(b); indeg[b]++;});
 const layer=new Array(n).fill(-1);
 let frontier=[]; indeg.forEach((d,i)=>{if(d===0)frontier.push(i);});
 if(!frontier.length && n)frontier=[0];
 frontier.forEach(i=>layer[i]=0);
 for(let depth=1; frontier.length && depth<n+1; depth++){
  const next=[];
  frontier.forEach(i=>adj[i].forEach(j=>{
   if(layer[j]<0){layer[j]=depth; next.push(j);}}));
  frontier=next;
 }
 layer.forEach((l,i)=>{if(l<0)layer[i]=0;});
 return layer;
}
function drawGraph(g){
 if(!g.nodes.length)return '(no units)';
 const layer=layers(g), cols={};
 g.nodes.forEach((nd,i)=>{(cols[layer[i]]=cols[layer[i]]||[]).push(i);});
 const cw=170, rh=48, bw=130, bh=30, pos={};
 Object.entries(cols).forEach(([l,ids])=>ids.forEach((id,r)=>{
  pos[id]=[l*cw+10, r*rh+12];}));
 const W=(Math.max(...Object.keys(cols).map(Number))+1)*cw+20;
 const H=Math.max(...Object.values(cols).map(c=>c.length))*rh+24;
 let s='<svg width="'+W+'" height="'+H+'">';
 s+='<defs><marker id="arr" markerWidth="7" markerHeight="7" refX="6" '+
  'refY="2.5" orient="auto"><path d="M0,0 L6,2.5 L0,5 z" fill="#888"/>'+
  '</marker></defs>';
 g.edges.forEach(([a,b])=>{
  const p=pos[a], q=pos[b], back=q[0]<=p[0];
  const x1=p[0]+(back?0:bw), y1=p[1]+bh/2, x2=q[0]+(back?bw:0),
   y2=q[1]+bh/2, bend=back?36:0;
  s+='<path d="M'+x1+','+y1+' C'+(x1+(back?-bend:40))+','+(y1+bend)+' '+
   (x2+(back?bend:-40))+','+(y2+bend)+' '+x2+','+y2+
   '" fill="none" stroke="'+(back?'#c60':'#888')+
   '" stroke-dasharray="'+(back?'4 3':'none')+'" marker-end="url(#arr)"/>';
 });
 g.nodes.forEach((nd,i)=>{
  const [x,y]=pos[i], heat=Math.min(nd.share*1.6,1);
  s+='<g class="node"><rect x="'+x+'" y="'+y+'" width="'+bw+'" height="'+
   bh+'" rx="5" fill="rgba(255,140,0,'+heat.toFixed(3)+
   ')" stroke="#555"><title>'+nd.cls+': '+nd.runs+' runs, '+
   nd.time+'s ('+(nd.share*100).toFixed(1)+'%)</title></rect>'+
   '<text x="'+(x+6)+'" y="'+(y+13)+'">'+nd.name.slice(0,19)+'</text>'+
   '<text x="'+(x+6)+'" y="'+(y+25)+'" fill="#666">'+nd.runs+'x '+
   nd.time.toFixed(2)+'s</text></g>';
 });
 return s+'</svg>';
}
function drawTimeline(evs){
 const spans=[], open={}, ticks=[];
 evs.forEach(e=>{
  const key=e.cat+':'+e.name;
  if(e.type==='begin')open[key]=e.time;
  else if(e.type==='end' && open[key]!==undefined){
   spans.push([key, open[key], e.time]); delete open[key];
  }else if(e.type==='single')ticks.push([key, e.time]);
 });
 const all=spans.map(s=>s[1]).concat(spans.map(s=>s[2]),
                                     ticks.map(t=>t[1]));
 if(!all.length)return '(no events yet)';
 const t0=Math.min(...all), t1=Math.max(...all), span=(t1-t0)||1;
 const lanes=[...new Set(spans.concat(ticks).map(s=>s[0]))].slice(0,12);
 const W=760, lh=20, X=t=>170+(t-t0)*(W-180)/span;
 let s='<svg width="'+W+'" height="'+(lanes.length*lh+24)+'">';
 lanes.forEach((ln,r)=>{
  const y=r*lh+14;
  s+='<text class="lane" x="2" y="'+(y+9)+'">'+ln.slice(0,26)+'</text>';
  spans.filter(sp=>sp[0]===ln).forEach(sp=>{
   s+='<rect x="'+X(sp[1])+'" y="'+y+'" width="'+
    Math.max(X(sp[2])-X(sp[1]),1.5)+'" height="12" fill="#06c" '+
    'opacity="0.65"><title>'+ln+' '+((sp[2]-sp[1])*1000).toFixed(1)+
    'ms</title></rect>';});
  ticks.filter(t=>t[0]===ln).forEach(t=>{
   s+='<circle cx="'+X(t[1])+'" cy="'+(y+6)+'" r="2.5" fill="#c60"/>';});
 });
 return s+'</svg>';
}
async function refresh(){
 try{   // health badge: green = alive, red = watchdog tripped (503)
  const hr=await fetch('/api/health'); const h=await hr.json();
  const bad=hr.status===503, wd=h.watchdog||{};
  document.getElementById('health').innerHTML=
   '<span style="font-size:13px;padding:2px 8px;border-radius:4px;'+
   'color:#fff;background:'+(bad?'#c00':'#2a2')+'">'+
   (bad?'WATCHDOG TRIPPED':'healthy')+'</span> <small>p'+
   esc(h.process_index)+' '+esc(h.mode||'?')+
   (h.last_progress_age_s!=null?
    ' · last step '+h.last_progress_age_s.toFixed(0)+'s ago':'')+
   (wd.armed?' · watchdog '+wd.window_s+'s':'')+
   (h.crashdumps?' · <b>'+h.crashdumps+' crashdump(s)</b>':'')+
   ' (<a href="/api/health">json</a>)</small>';
 }catch(e){}
 const s=await (await fetch('/api/status')).json();
 document.getElementById('status').innerHTML =
  '<pre>'+JSON.stringify(s,null,2)+'</pre>';
 if(s.serving){
  const c=s.serving.continuous||s.serving;
  const rows=Object.entries(c)
   .filter(([k,v])=>typeof v!=='object')
   .map(([k,v])=>'<tr><td>'+esc(k)+'</td><td>'+esc(v)+'</td></tr>')
   .join('');
  // client-side ring buffer -> live time-series of the SLO gauges
  // (one sample per refresh; the server only ever sends a snapshot)
  window._srv=window._srv||{};
  for(const k of ['agg_tokens_per_sec','queued','in_flight',
                  'p99_queue_wait_ms']){
   if(typeof c[k]==='number'){
    (window._srv[k]=window._srv[k]||[]).push([0,c[k]]);
    if(window._srv[k].length>120)window._srv[k].shift();
   }
  }
  const sparks=Object.entries(window._srv)
   .filter(([k,pts])=>pts.length>1)
   .map(([k,pts])=>sparkSpan(k,pts)).join('');
  document.getElementById('serving').innerHTML=
   sparks+'<table>'+rows+'</table>';
 }
 const m=await (await fetch('/api/metrics')).json();
 document.getElementById('metrics').innerHTML =
  Object.entries(m).map(([k,pts])=>sparkSpan(k,pts)).join('')
  || '(no epoch metrics yet)';
 const tl=await (await fetch('/api/telemetry')).json();
 const mfu=(tl.records||[]).filter(r=>r.kind==='mfu').pop();
 document.getElementById('mfu').innerHTML = mfu ?
  '<b>MFU</b> predicted '+mfu.predicted.toPrecision(3)+
  ' measured '+mfu.measured.toPrecision(3)+
  ' ratio '+mfu.ratio.toPrecision(3)+
  (mfu.warned?' <b style="color:#c00">SHORTFALL</b>':' ok')+
  ' <small>('+esc(mfu.device)+' roofline)</small>' : '';
 const trows=(tl.metrics||[]).filter(s=>s.kind!=='histogram')
  .slice(0,60)
  .map(s=>'<tr><td>'+esc(s.name)+'</td><td>'+
   esc(Object.entries(s.labels).map(([k,v])=>k+'='+v).join(','))+
   '</td><td align=right>'+
   (typeof s.value==='number'?s.value.toPrecision(5):esc(s.value))+
   '</td></tr>').join('');
 document.getElementById('telemetry').innerHTML = trows ?
  '<table><tr><th align=left>metric</th><th>labels</th>'+
  '<th>value</th></tr>'+trows+'</table>' : '(no samples yet)';
 const g=await (await fetch('/api/graph')).json();
 document.getElementById('graph').innerHTML =
  Object.entries(g).map(([name,wf])=>
   '<b>'+name+'</b><br>'+drawGraph(wf)).join('<br>') || '(no workflows)';
 const e=await (await fetch('/api/events')).json();
 document.getElementById('timeline').innerHTML = drawTimeline(e);
 document.getElementById('events').innerHTML =
  '<pre>'+e.slice(-30).map(x=>JSON.stringify(x)).join('\\n')+'</pre>';
}
refresh(); setInterval(refresh, 3000);
</script></body></html>"""


class WebStatusServer(Logger):
    def __init__(self, host=None, port=None):
        super(WebStatusServer, self).__init__()
        # explicit args win; the root.common.web knobs are the defaults
        # (--web-status PORT passes the port explicitly)
        from veles_tpu.config import root
        if host is None:
            host = str(root.common.web.get("host", "127.0.0.1"))
        if port is None:
            port = int(root.common.web.get("port", 8090))
        self.host, self.port = host, port
        self._workflows = {}
        self._serving = None
        self._updates = []
        self._server = None
        self._thread = None
        self._profile = {}
        self._lock = threading.Lock()

    def register(self, workflow):
        """Track a local workflow; its gather_results() feeds /api/status."""
        with self._lock:
            self._workflows[workflow.name] = workflow

    def register_serving(self, api):
        """Track a serving endpoint (RESTfulAPI or anything with
        ``serving_metrics()``/``metrics()``): its latency/throughput
        snapshot joins ``/api/status`` under ``"serving"`` and feeds
        the dashboard's serving panel."""
        with self._lock:
            self._serving = api

    def metrics(self, limit=200):
        """Per-epoch metric time series from the event ring: every
        numeric field of an ``epoch`` event becomes
        {series: [[epoch, value], ...]} — the dashboard's sparklines
        (ref the node.js status app's live charts, web/)."""
        skip = {"name", "cat", "type", "time", "epoch"}
        series = {}
        for ev in events.snapshot():
            if ev.get("name") != "epoch":
                continue
            ep = ev.get("epoch", 0)
            for k, v in ev.items():
                # non-finite values would serialize as the literal NaN,
                # which strict browser-side JSON.parse rejects
                if (k not in skip and isinstance(v, (int, float))
                        and math.isfinite(v)):
                    series.setdefault(k, []).append([ep, v])
        return {k: v[-limit:] for k, v in series.items()}

    def graph(self):
        """Control-graph JSON per registered workflow: nodes carry class,
        run count/time and run-time share (the dashboard heat-colors
        them), edges are the control links — the live equivalent of the
        reference's workflow SVG shipped in status POSTs
        (launcher.py:852-885)."""
        out = {}
        with self._lock:
            for name, wf in self._workflows.items():
                units = wf.units
                ids = {u: i for i, u in enumerate(units)}
                total = sum(u.run_time for u in units) or 1.0
                out[name] = {
                    "nodes": [{"id": i, "name": u.name,
                               "cls": type(u).__name__,
                               "runs": u.run_count,
                               "time": round(u.run_time, 4),
                               "share": round(u.run_time / total, 4)}
                              for u, i in ids.items()],
                    "edges": [[ids[u], ids[d]] for u in units
                              for d in u.links_to if d in ids],
                }
        return out

    def dot(self):
        """Concatenated DOT text of every registered workflow."""
        with self._lock:
            return "\n".join(wf.generate_graph()
                             for wf in self._workflows.values())

    @staticmethod
    def chrome_trace():
        """The event ring as a Chrome trace (chrome://tracing /
        Perfetto `trace.json`): begin/end pairs → B/E duration events,
        singles → instant events, lanes keyed by event category — the
        reference's Mongo event timeline as a standard tooling format."""
        out = []
        for ev in events.snapshot():
            ph = {"begin": "B", "end": "E", "single": "i"}.get(
                ev.get("type"))
            if ph is None:
                continue
            rec = {"name": ev.get("name", "?"), "ph": ph,
                   "ts": float(ev.get("time", 0.0)) * 1e6,   # µs
                   "pid": 0, "tid": ev.get("cat", "events")}
            if ph == "i":
                rec["s"] = "t"
            # finite numbers only — a NaN arg would serialize as the
            # bare literal NaN, which strict parsers (Perfetto,
            # JSON.parse) reject wholesale (same guard as metrics())
            extra = {k: v for k, v in ev.items()
                     if k not in ("name", "cat", "type", "time")
                     and isinstance(v, (int, float, str, bool))
                     and (not isinstance(v, float) or math.isfinite(v))}
            if extra:
                rec["args"] = extra
            out.append(rec)
        return {"traceEvents": out, "displayTimeUnit": "ms"}

    def profile_capture(self, seconds=3.0, outdir=None):
        """On-demand ``jax.profiler`` window over the LIVE process —
        the step timeline of where device time actually goes (TPU ops,
        HBM transfers, host dispatch), captured from the dashboard
        without restarting with ``--profile``.  The capture runs on a
        background thread; whatever the training loop executes during
        the window lands in the trace."""
        from veles_tpu.config import root
        with self._lock:
            if self._profile.get("running"):
                return {"error": "capture already running",
                        "dir": self._profile.get("dir")}
            d = outdir or os.path.join(
                root.common.dirs.get("profiles", "profiles"),
                time.strftime("web_%Y%m%d_%H%M%S"))
            self._profile = {"running": True, "dir": d,
                             "seconds": float(seconds)}

        def capture():
            import jax
            try:
                jax.profiler.start_trace(d)
                try:
                    time.sleep(float(seconds))
                finally:
                    # the profiler is a process-global singleton: an
                    # exception mid-window (interrupted sleep, writer
                    # error) must still stop the trace, or every later
                    # capture fails with "profiler already running"
                    jax.profiler.stop_trace()
                state = {"running": False, "dir": d,
                         "done_at": time.time()}
            except Exception as e:   # noqa: BLE001 — surface via GET
                state = {"running": False, "dir": d, "error": str(e)}
            with self._lock:
                self._profile = state

        threading.Thread(target=capture, daemon=True).start()
        return {"ok": True, "dir": d, "seconds": float(seconds)}

    def profile_trace(self):
        """The latest capture's chrome-trace JSON bytes (the profiler's
        ``*.trace.json.gz``, decompressed — loadable in Perfetto), or
        None when nothing has been captured."""
        import glob
        import gzip
        with self._lock:
            d = self._profile.get("dir")
            if not d or self._profile.get("running"):
                return None
        paths = sorted(glob.glob(os.path.join(
            d, "plugins", "profile", "*", "*.trace.json.gz")))
        if not paths:
            return None
        with gzip.open(paths[-1], "rb") as f:
            return f.read()

    def _log_db(self):
        from veles_tpu.config import root
        return root.common.web.get("log_db", None)

    def log_runs(self):
        """Cross-run session index from the sqlite log store (the
        reference's historical log browser, ref web_status.py:113-200 +
        the Mongo duplication it reads, logger.py:292-331)."""
        db = self._log_db()
        if not db or not os.path.exists(db):
            return {"error": "no log db (run with --log-db PATH)",
                    "runs": []}
        from veles_tpu.logger import log_sessions
        return {"runs": log_sessions(db)}

    def log_search(self, session=None, q=None, level=None, limit=200):
        """Search records across every run in the log store."""
        db = self._log_db()
        if not db or not os.path.exists(db):
            return {"error": "no log db (run with --log-db PATH)",
                    "logs": []}
        from veles_tpu.logger import search_logs
        return {"logs": search_logs(db, session=session, q=q,
                                    level=level, limit=limit)}

    def bench_report(self):
        """Predicted-vs-measured perf panel data: the newest value of
        every bench row in the process performance ledger
        (telemetry.ledger — the one record of speed) next to the
        offline roofline model's predictions (tools/cost_model.py; ref:
        the autotune DB as the reference's measurement store,
        veles/backends.py:672-731)."""
        from veles_tpu.telemetry import ledger
        book = ledger.default()
        measured, newest = {}, None
        for rec in book.records():
            if rec.get("metric") in ledger.BENCH_ROWS and isinstance(
                    rec.get("value"), (int, float)):
                measured[rec["metric"]] = rec["value"]
                newest = max(newest or 0.0, rec.get("ts") or 0.0)
        predicted = {}
        try:
            from tools.cost_model import predictions_for_bench
            predicted = predictions_for_bench()
        except Exception:   # noqa: BLE001 — model optional at runtime
            predicted = {}
        return {"measured": measured, "predicted": predicted,
                "measured_at": (time.strftime(
                    "%Y-%m-%d %H:%M:%S", time.localtime(newest))
                    if newest else None),
                "ledger": book.path}

    def perf_report(self):
        """``/api/perf`` payload: the persistent performance ledger
        (telemetry.ledger) grouped per key — trend values, latest
        sample, declared target, and the regression sentinel's verdict
        on that latest sample.  The sentinel's live gauges
        (``veles_perf_drift{metric}``,
        ``veles_perf_regressions_total``) ride the normal ``/metrics``
        Prometheus surface; this endpoint is the history view behind
        them.  Never raises — a perf panel that 500s hides the
        regression it exists to show."""
        try:
            from veles_tpu.telemetry import ledger
            book = ledger.default()
            keys = []
            for key, recs in sorted(book.by_key().items()):
                latest, prior = recs[-1], recs[:-1]
                verdict = book.assess(latest, prior)
                trend = [r.get("value") for r in recs[-32:]
                         if isinstance(r.get("value"), (int, float))]
                keys.append({"key": key,
                             "metric": latest.get("metric"),
                             "unit": latest.get("unit", ""),
                             "n": len(recs),
                             "last": latest.get("value"),
                             "ts": latest.get("ts"),
                             "trend": trend,
                             "verdict": verdict})
            return {"ledger": book.path, "keys": keys}
        except Exception as e:   # noqa: BLE001 — the panel must answer
            return {"error": str(e), "keys": []}

    def health_status(self):
        """``/api/health`` payload: process id/mode, last-step age,
        watchdog state, crashdump count (telemetry.health.status), plus
        — when a serving endpoint is registered — the lifecycle block
        (shed valve state, cancel/deadline/fault counters) under
        ``"serving"``, so an operator's probe sees load shedding the
        moment it starts.  Never raises — a health probe that 500s is
        worse than no probe."""
        try:
            from veles_tpu.telemetry import health
            state = health.status()
        except Exception as e:   # noqa: BLE001
            state = {"error": str(e), "watchdog": {"tripped": False}}
        with self._lock:
            serving = self._serving
        engine = getattr(serving, "engine", None)
        if engine is not None:
            try:
                state["serving"] = engine.lifecycle_status()
            except Exception as e:   # noqa: BLE001
                state["serving"] = {"error": str(e)}
        try:
            # pod-size block (threaded into workers by the pod master,
            # services.podmaster): probing ANY worker answers "how big
            # is the pod right now, and who is missing"
            from veles_tpu.config import root as _root
            pod = _root.common.get("pod")
            pod = pod.as_dict() if hasattr(pod, "as_dict") else None
            if pod and "size" in pod:
                state["pod"] = {
                    "size": pod.get("size"), "total": pod.get("total"),
                    "degraded": bool(pod.get("degraded")),
                    "lost_hosts": pod.get("lost_hosts") or []}
        except Exception:   # noqa: BLE001 — the probe must answer
            pass
        try:
            # fleet-membership block (env threaded in by the pod
            # agent, services.podmaster ServeFleetMaster): probing a
            # replica's dashboard answers "which fleet slot is this"
            host = os.environ.get("VELES_TPU_FLEET_HOST")
            rep = os.environ.get("VELES_TPU_FLEET_REP")
            role = os.environ.get("VELES_TPU_REPLICA_ROLE")
            if host is not None or rep is not None:
                state["fleet"] = {
                    "host": None if host is None else int(host),
                    "replica": None if rep is None else int(rep),
                    "role": role}
        except Exception:   # noqa: BLE001 — the probe must answer
            pass
        return state

    def status(self):
        out = {"time": time.time(), "workflows": {}, "remote": self._updates[-20:]}
        with self._lock:
            for name, wf in self._workflows.items():
                try:
                    out["workflows"][name] = wf.gather_results()
                except Exception as e:  # noqa: BLE001
                    out["workflows"][name] = {"error": str(e)}
            serving = self._serving
        if serving is not None:
            try:
                out["serving"] = (serving.serving_metrics()
                                  if hasattr(serving, "serving_metrics")
                                  else serving.metrics())
            except Exception as e:  # noqa: BLE001
                out["serving"] = {"error": str(e)}
        return out

    def start(self):
        server = self

        class Handler(BaseHTTPRequestHandler):
            def _send(self, code, body, ctype="application/json"):
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/":
                    self._send(200, _PAGE.encode(), "text/html")
                elif self.path == "/api/status":
                    self._send(200, json.dumps(server.status(),
                                               default=str).encode())
                elif self.path == "/api/events":
                    self._send(200, json.dumps(events.snapshot()[-200:],
                                               default=str).encode())
                elif self.path == "/api/metrics":
                    self._send(200, json.dumps(server.metrics(),
                                               default=str).encode())
                elif self.path == "/api/graph":
                    self._send(200, json.dumps(server.graph(),
                                               default=str).encode())
                elif self.path == "/api/dot":
                    self._send(200, server.dot().encode(), "text/plain")
                elif self.path == "/api/trace":
                    self._send(200, json.dumps(
                        server.chrome_trace()).encode())
                elif self.path.startswith("/api/trace/"):
                    # per-request span store (telemetry.tracing):
                    # this process's leg of a serving request's
                    # cross-process timeline, keyed by trace id
                    from veles_tpu.telemetry import tracing
                    tid = self.path[len("/api/trace/"):]
                    spans = tracing.store.spans(tid)
                    self._send(
                        200 if spans else 404,
                        json.dumps(
                            {"trace": tid, "spans": spans,
                             "phases": tracing.phases_of(spans)}
                        ).encode())
                elif self.path == "/api/plots":
                    self._send(200, json.dumps(bus.snapshot()[-20:],
                                               default=str).encode())
                elif self.path == "/api/profile":
                    with server._lock:
                        state = dict(server._profile)
                    self._send(200, json.dumps(state,
                                               default=str).encode())
                elif self.path == "/api/profile/trace":
                    body = server.profile_trace()
                    if body is None:
                        self._send(404, b'{"error": "no capture yet"}')
                    else:
                        self._send(200, body)
                elif self.path == "/metrics":
                    # Prometheus scrape surface (text format 0.0.4)
                    self._send(200,
                               telemetry.registry.render_prometheus()
                               .encode(),
                               "text/plain; version=0.0.4; "
                               "charset=utf-8")
                elif self.path == "/api/telemetry":
                    self._send(200, json.dumps(
                        {"metrics": telemetry.registry.snapshot(),
                         "records": telemetry.registry.records()[-60:]},
                        default=str).encode())
                elif self.path == "/api/health":
                    # liveness/forensics surface (telemetry.health):
                    # 503 once the hang watchdog has tripped, so a
                    # k8s-style probe (or a human's curl) distinguishes
                    # "serving but stalled" from healthy
                    state = server.health_status()
                    self._send(
                        503 if state.get("watchdog", {}).get("tripped")
                        else 200,
                        json.dumps(state, default=str).encode())
                elif self.path == "/api/bench":
                    self._send(200, json.dumps(server.bench_report(),
                                               default=str).encode())
                elif self.path == "/api/perf":
                    self._send(200, json.dumps(server.perf_report(),
                                               default=str).encode())
                elif self.path.startswith("/api/logruns"):
                    self._send(200, json.dumps(
                        server.log_runs(), default=str).encode())
                elif self.path.startswith("/api/logs"):
                    from urllib.parse import parse_qs, urlsplit
                    qs = {k: v[0] for k, v in parse_qs(
                        urlsplit(self.path).query).items()}
                    try:
                        limit = min(int(qs.get("limit", 200)), 10000)
                    except ValueError:
                        limit = 200
                    self._send(200, json.dumps(server.log_search(
                        session=qs.get("session"), q=qs.get("q"),
                        level=qs.get("level"), limit=limit),
                        default=str).encode())
                elif self.path == "/frontend":
                    # the command-composer page, generated live from the
                    # CLI arg registry (ref --frontend, launcher.py:199-267)
                    from veles_tpu.scripts import generate_frontend as gf
                    page = gf.render(gf.describe_parser(gf._main_parser()))
                    self._send(200, page.encode(), "text/html")
                else:
                    self.send_error(404)

            def do_POST(self):
                if self.path == "/api/profile":
                    length = int(self.headers.get("Content-Length", 0))
                    try:
                        req = json.loads(self.rfile.read(length) or b"{}")
                    except ValueError:
                        req = {}
                    if not isinstance(req, dict):
                        req = {}
                    try:
                        seconds = float(req.get("seconds", 3.0))
                    except (TypeError, ValueError):
                        self._send(400, b'{"error": "bad seconds"}')
                        return
                    # bound the window: the capture slot is singular and
                    # profiler overhead rides the live training loop
                    out = server.profile_capture(
                        seconds=min(max(seconds, 0.1), 60.0))
                    self._send(200, json.dumps(out).encode())
                    return
                # remote master update (ref web_status '/update' POST)
                if self.path != "/update":
                    self.send_error(404)
                    return
                length = int(self.headers.get("Content-Length", 0))
                try:
                    update = json.loads(self.rfile.read(length))
                except ValueError:
                    self._send(400, b'{"error": "bad json"}')
                    return
                with server._lock:
                    server._updates.append(
                        {"time": time.time(), "update": update})
                self._send(200, b'{"ok": true}')

            def log_message(self, fmt, *args):
                server.debug("http: " + fmt, *args)

        # /metrics is now scrapeable: turn on the costly collections
        # (device-memory census) that are otherwise skipped
        telemetry.enable_collection()
        self._server = ThreadingHTTPServer((self.host, self.port), Handler)
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        daemon=True)
        self._thread.start()
        self.info("web status on http://%s:%d/", self.host, self.port)

    def stop(self):
        if self._server is not None:
            self._server.shutdown()
            self._server = None
