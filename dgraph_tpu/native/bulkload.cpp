// Native bulk-load pipeline: the map/shuffle/reduce hot path of
// loaders/bulk2.py (ref dgraph/cmd/bulk loader.go mapStage/reduceStage)
// in C++. The Python orchestrator owns schema, xid lease, storage
// ingest and every uncommon line shape (facets, @lang, typed literals,
// non-ASCII, exotic tokenizers) — those lines are returned as "slow"
// text and run through the Python mapper into the same run format, so
// the native reduce merges both.
//
// Byte formats replicated EXACTLY (shared storage formats):
//   keys:     x/keys.py        [tag][len u16 BE][ns u64 BE + attr][kind][suffix]
//   runs:     loaders/bulk2.py  _REC = <HBI> klen kind plen
//   postings: posting/pl.py    _enc_posting wire layout
//   uid pack: codec/uidpack.py serialize_uids (magic UPK1, bitpacked)
//   tokens:   tok/tok.py       ident-byte-prefixed token bytes
//   farmhash: utils/farmhash.py Fingerprint64 (public FarmHash spec)

#include <algorithm>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <cstdlib>
#include <map>
#include <queue>
#include <set>
#include <string>
#include <unordered_map>
#include <vector>

#include "sst_bloom.h"

namespace {

using u8 = uint8_t;
using u16 = uint16_t;
using u32 = uint32_t;
using u64 = uint64_t;
using i64 = int64_t;

// ---------------------------------------------------------------------------
// FarmHash Fingerprint64 (port of utils/farmhash.py, public spec)
// ---------------------------------------------------------------------------

constexpr u64 K0 = 0xC3A5C85C97CB3127ULL;
constexpr u64 K1 = 0xB492B66FBE98F273ULL;
constexpr u64 K2 = 0x9AE16A3B2F90404FULL;

static inline u64 rot(u64 v, int s) { return s == 0 ? v : (v >> s) | (v << (64 - s)); }
static inline u64 smix(u64 v) { return v ^ (v >> 47); }
static inline u64 f64(const u8* s, size_t i) { u64 v; memcpy(&v, s + i, 8); return v; }
static inline u64 f32(const u8* s, size_t i) { u32 v; memcpy(&v, s + i, 4); return v; }

static u64 h16(u64 u, u64 v, u64 mul) {
  u64 a = (u ^ v) * mul; a ^= a >> 47;
  u64 b = (v ^ a) * mul; b ^= b >> 47;
  return b * mul;
}

static u64 len0to16(const u8* s, size_t n) {
  if (n >= 8) {
    u64 mul = K2 + n * 2;
    u64 a = f64(s, 0) + K2, b = f64(s, n - 8);
    u64 c = rot(b, 37) * mul + a, d = (rot(a, 25) + b) * mul;
    return h16(c, d, mul);
  }
  if (n >= 4) {
    u64 mul = K2 + n * 2, a = f32(s, 0);
    return h16(n + (a << 3), f32(s, n - 4), mul);
  }
  if (n > 0) {
    u64 a = s[0], b = s[n >> 1], c = s[n - 1];
    u64 y = a + (b << 8), z = n + (c << 2);
    return smix(y * K2 ^ z * K0) * K2;
  }
  return K2;
}

static u64 len17to32(const u8* s, size_t n) {
  u64 mul = K2 + n * 2;
  u64 a = f64(s, 0) * K1, b = f64(s, 8);
  u64 c = f64(s, n - 8) * mul, d = f64(s, n - 16) * K2;
  return h16(rot(a + b, 43) + rot(c, 30) + d, a + rot(b + K2, 18) + c, mul);
}

static u64 len33to64(const u8* s, size_t n) {
  u64 mul = K2 + n * 2;
  u64 a = f64(s, 0) * K2, b = f64(s, 8);
  u64 c = f64(s, n - 8) * mul, d = f64(s, n - 16) * K2;
  u64 y = rot(a + b, 43) + rot(c, 30) + d;
  u64 z = h16(y, a + rot(b + K2, 18) + c, mul);
  u64 e = f64(s, 16) * mul, f = f64(s, 24);
  u64 g = (y + f64(s, n - 32)) * mul, h = (z + f64(s, n - 24)) * mul;
  return h16(rot(e + f, 43) + rot(g, 30) + h, e + rot(f + a, 18) + g, mul);
}

static void weak32(const u8* s, size_t i, u64 a, u64 b, u64* oa, u64* ob) {
  u64 w = f64(s, i), x = f64(s, i + 8), y = f64(s, i + 16), z = f64(s, i + 24);
  a += w;
  b = rot(b + a + z, 21);
  u64 c = a;
  a += x + y;
  b += rot(a, 44);
  *oa = a + z;
  *ob = b + c;
}

static u64 farm64(const u8* s, size_t n) {
  if (n <= 16) return len0to16(s, n);
  if (n <= 32) return len17to32(s, n);
  if (n <= 64) return len33to64(s, n);
  u64 seed = 81;
  u64 x = seed, y = seed * K1 + 113;
  u64 z = smix(y * K2 + 113) * K2;
  u64 v1 = 0, v2 = 0, w1 = 0, w2 = 0;
  x = x * K2 + f64(s, 0);
  size_t end = ((n - 1) / 64) * 64, last64 = n - 64, i = 0;
  while (i < end) {
    x = rot(x + y + v1 + f64(s, i + 8), 37) * K1;
    y = rot(y + v2 + f64(s, i + 48), 42) * K1;
    x ^= w2;
    y = y + v1 + f64(s, i + 40);
    z = rot(z + w1, 33) * K1;
    weak32(s, i, v2 * K1, x + w1, &v1, &v2);
    weak32(s, i + 32, z + w2, y + f64(s, i + 16), &w1, &w2);
    std::swap(z, x);
    i += 64;
  }
  u64 mul = K1 + ((z & 0xFF) << 1);
  i = last64;
  w1 += (n - 1) & 63;
  v1 += w1;
  w1 += v1;
  x = rot(x + y + v1 + f64(s, i + 8), 37) * mul;
  y = rot(y + v2 + f64(s, i + 48), 42) * mul;
  x ^= w2 * 9;
  y = y + v1 * 9 + f64(s, i + 40);
  z = rot(z + w1, 33) * mul;
  weak32(s, i, v2 * mul, x + w1, &v1, &v2);
  weak32(s, i + 32, z + w2, y + f64(s, i + 16), &w1, &w2);
  std::swap(z, x);
  return h16(h16(v1, w1, mul) + smix(y) * K0 + z, h16(v2, w2, mul) + x, mul);
}

// ---------------------------------------------------------------------------
// Schema / value plumbing
// ---------------------------------------------------------------------------

// TypeID values (types/types.py)
enum { T_DEFAULT = 0, T_BINARY = 1, T_INT = 2, T_FLOAT = 3, T_BOOL = 4,
       T_DATETIME = 5, T_GEO = 6, T_UID = 7, T_STRING = 9 };

// tokenizer identifier bytes (tok/tok.py)
enum { TOK_TERM = 0x1, TOK_EXACT = 0x2, TOK_YEAR = 0x4, TOK_MONTH = 0x41,
       TOK_DAY = 0x42, TOK_HOUR = 0x43, TOK_INT = 0x6, TOK_FLOAT = 0x7,
       TOK_FULLTEXT = 0x8, TOK_BOOL = 0x9 };

constexpr u64 VALUE_UID = ~0ULL;
constexpr u8 OP_SET = 1;
constexpr u8 K_UID = 0, K_VAL = 1, K_IDX = 2;

struct Pred {
  u8 value_type = T_DEFAULT;
  bool is_list = false, reverse = false, count = false, has_lang = false;
  std::vector<u8> toks;  // supported tokenizer ids only
  std::string data_prefix, rev_prefix, idx_prefix;  // precomputed key heads
};

static void put_u16be(std::string& o, u16 v) { o.push_back(char(v >> 8)); o.push_back(char(v & 0xFF)); }
static void put_u64be(std::string& o, u64 v) { for (int i = 7; i >= 0; --i) o.push_back(char((v >> (8 * i)) & 0xFF)); }
static void put_u32le(std::string& o, u32 v) { o.append((const char*)&v, 4); }
static void put_u64le(std::string& o, u64 v) { o.append((const char*)&v, 8); }

// key head: [0x00][len u16 BE][ns u64 BE + attr] + kind byte
static std::string key_head(u64 ns, const std::string& attr, u8 kind) {
  std::string o;
  o.push_back('\x00');
  put_u16be(o, u16(8 + attr.size()));
  put_u64be(o, ns);
  o += attr;
  o.push_back(char(kind));
  return o;
}

struct Entry {
  std::string key;
  u8 kind;
  std::string payload;
  bool operator<(const Entry& b) const {
    if (key != b.key) return key < b.key;
    if (kind != b.kind) return kind < b.kind;
    return payload < b.payload;
  }
};

struct Ctx {
  std::unordered_map<std::string, u64> xids;
  std::vector<std::string> xid_order;  // sorted, for assignment
  u64 base = 0;
  std::unordered_map<std::string, Pred> preds;
  u64 nquads = 0;
  std::vector<std::string> runs;
  std::string err;
};

// ---------------------------------------------------------------------------
// Value conversion + posting/token emission
// ---------------------------------------------------------------------------

struct DT { int y=0, mo=1, d=1, h=0, mi=0, s=0; long micro=0; bool tz=false; int tzmin=0; };

static bool parse_dt(const char* p, size_t n, DT* o) {
  // YYYY[-MM[-DD[THH:MM:SS[.ffffff][Z|+HH:MM]]]]
  auto num = [&](size_t i, size_t len, int* out) {
    int v = 0;
    for (size_t k = i; k < i + len; ++k) {
      if (k >= n || p[k] < '0' || p[k] > '9') return false;
      v = v * 10 + (p[k] - '0');
    }
    *out = v;
    return true;
  };
  if (!num(0, 4, &o->y)) return false;
  size_t i = 4;
  if (i == n) return true;
  if (p[i] != '-' || !num(i + 1, 2, &o->mo)) return false;
  i += 3;
  if (i == n) return true;
  if (p[i] != '-' || !num(i + 1, 2, &o->d)) return false;
  i += 3;
  if (i == n) return true;
  if (p[i] != 'T' && p[i] != ' ') return false;
  if (!num(i + 1, 2, &o->h)) return false;
  if (p[i + 3] != ':' || !num(i + 4, 2, &o->mi)) return false;
  if (p[i + 6] != ':' || !num(i + 7, 2, &o->s)) return false;
  i += 9;
  if (i < n && p[i] == '.') {
    size_t j = i + 1; long frac = 0; int digits = 0;
    while (j < n && p[j] >= '0' && p[j] <= '9' && digits < 9) {
      frac = frac * 10 + (p[j] - '0'); ++digits; ++j;
    }
    while (digits < 6) { frac *= 10; ++digits; }
    while (digits > 6) { frac /= 10; --digits; }
    o->micro = frac;
    i = j;
  }
  if (i == n) return true;
  if (p[i] == 'Z' && i + 1 == n) { o->tz = true; o->tzmin = 0; return true; }
  if ((p[i] == '+' || p[i] == '-') && i + 6 == n) {
    int hh, mm;
    if (!num(i + 1, 2, &hh) || p[i + 3] != ':' || !num(i + 4, 2, &mm)) return false;
    o->tz = true;
    o->tzmin = (hh * 60 + mm) * (p[i] == '-' ? -1 : 1);
    return true;
  }
  return false;
}

// matches datetime.isoformat() of parse_datetime(s)
static std::string dt_isoformat(const DT& d) {
  char buf[64];
  int len = snprintf(buf, sizeof buf, "%04d-%02d-%02dT%02d:%02d:%02d",
                     d.y, d.mo, d.d, d.h, d.mi, d.s);
  std::string o(buf, len);
  if (d.micro) {
    len = snprintf(buf, sizeof buf, ".%06ld", d.micro);
    o.append(buf, len);
  }
  if (d.tz) {
    int m = d.tzmin, am = m < 0 ? -m : m;
    len = snprintf(buf, sizeof buf, "%c%02d:%02d", m < 0 ? '-' : '+', am / 60, am % 60);
    o.append(buf, len);
  }
  return o;
}

static i64 days_from_civil(int y, int m, int d) {
  y -= m <= 2;
  i64 era = (y >= 0 ? y : y - 399) / 400;
  unsigned yoe = unsigned(y - era * 400);
  unsigned doy = (153 * (m + (m > 2 ? -3 : 9)) + 2) / 5 + d - 1;
  unsigned doe = yoe * 365 + yoe / 4 - yoe / 100 + doy;
  return era * 146097 + i64(doe) - 719468;  // days since 1970-01-01
}

// Go time.MarshalBinary v1 (utils/farmhash.py go_time_binary)
static std::string go_time_binary(const DT& d) {
  i64 unix_s = days_from_civil(d.y, d.mo, d.d) * 86400 + d.h * 3600 + d.mi * 60 + d.s;
  int offmin;
  if (!d.tz) offmin = -1;
  else { unix_s -= i64(d.tzmin) * 60; offmin = d.tzmin == 0 ? -1 : d.tzmin; }
  // RFC3339 "+00:00"/"Z" parse to the UTC singleton in Python => -1
  const i64 UNIX_TO_INTERNAL = (1969LL * 365 + 1969 / 4 - 1969 / 100 + 1969 / 400) * 86400;
  i64 sec = unix_s + UNIX_TO_INTERNAL;
  i64 nsec = d.micro * 1000;
  std::string o;
  o.push_back('\x01');
  put_u64be(o, u64(sec));
  o.push_back(char((nsec >> 24) & 0xFF)); o.push_back(char((nsec >> 16) & 0xFF));
  o.push_back(char((nsec >> 8) & 0xFF)); o.push_back(char(nsec & 0xFF));
  o.push_back(char((offmin >> 8) & 0xFF)); o.push_back(char(offmin & 0xFF));
  return o;
}

// sortable int token payload (tok.py _enc_int_sortable)
static std::string enc_int_sortable(i64 x) {
  std::string o;
  put_u64be(o, u64(x) + 0x8000000000000000ULL);
  return o;
}

static const char* STOPWORDS[] = {
  "a","an","and","are","as","at","be","by","for","from","has","he","in","is",
  "it","its","of","on","that","the","to","was","were","will","with","this",
  "those","these","you","your","i","we","they","them","he","she","our","not",
  "no","or","but","if","then","so","what","which","who","whom", nullptr};

static bool is_stopword(const std::string& w) {
  for (int i = 0; STOPWORDS[i]; ++i)
    if (w == STOPWORDS[i]) return true;
  return false;
}

// tok.py _porter_stem (tiny suffix stripper)
static std::string porter_stem(std::string w) {
  static const char* SUF[] = {"ingly","edly","ing","ed","ly","ies","es","s", nullptr};
  for (int i = 0; SUF[i]; ++i) {
    size_t sl = strlen(SUF[i]);
    if (w.size() >= sl && w.size() - sl >= 3 &&
        w.compare(w.size() - sl, sl, SUF[i]) == 0) {
      w.resize(w.size() - sl);
      if (strcmp(SUF[i], "ies") == 0) w += "y";
      break;
    }
  }
  return w;
}

// ASCII word split + lowercase ([\w']+ on pre-checked ASCII text)
static std::vector<std::string> words_ascii(const char* p, size_t n) {
  std::vector<std::string> out;
  std::string cur;
  for (size_t i = 0; i < n; ++i) {
    char c = p[i];
    bool wc = (c >= 'a' && c <= 'z') || (c >= 'A' && c <= 'Z') ||
              (c >= '0' && c <= '9') || c == '_' || c == '\'';
    if (wc) cur.push_back(c >= 'A' && c <= 'Z' ? c + 32 : c);
    else if (!cur.empty()) { out.push_back(cur); cur.clear(); }
  }
  if (!cur.empty()) out.push_back(cur);
  return out;
}

// posting/pl.py _enc_posting: fast-path value posting (no lang/facets)
static std::string enc_value_posting(u64 puid, u8 tid, const std::string& v) {
  std::string o;
  o.push_back(char(1 | (OP_SET << 1)));
  put_u64le(o, puid);
  o.push_back(char(tid));
  o.push_back('\x00');            // lang len
  put_u32le(o, u32(v.size()));
  o += v;
  o.push_back('\x00'); o.push_back('\x00');  // facet count u16
  return o;
}

// ---------------------------------------------------------------------------
// uid pack serialization (codec/uidpack.py serialize_uids / serialize)
// ---------------------------------------------------------------------------

static int width_bits(const u32* v, size_t n) {
  u32 mx = 0;
  for (size_t i = 0; i < n; ++i) mx = std::max(mx, v[i]);
  int w = 0;
  while ((1ULL << w) <= mx) ++w;  // bit_length of max
  return mx == 0 ? 0 : w;
}

static void bitpack_into(const u32* vals, size_t n, int width, std::string& out) {
  if (width == 0 || n == 0) return;
  size_t nbytes = (n * width + 7) / 8;
  size_t start = out.size();
  out.resize(start + nbytes, 0);
  u8* buf = (u8*)out.data() + start;
  size_t bit = 0;
  for (size_t i = 0; i < n; ++i) {
    u64 v = vals[i];
    size_t byte = bit >> 3;
    int sh = bit & 7;
    u64 cur = v << sh;
    for (int b = 0; b < 5 && byte + b < nbytes; ++b)
      buf[byte + b] |= u8((cur >> (8 * b)) & 0xFF);
    bit += width;
  }
}

static void serialize_uids(const std::vector<u64>& u, std::string& out) {
  out += "UPK1";
  size_t n = u.size();
  if (n == 0) { put_u64le(out, 0); put_u32le(out, 0); return; }
  // block split: <=256 per block, never spanning a hi-32 boundary
  std::vector<std::pair<size_t, size_t>> blocks;  // (start, count)
  size_t i = 0;
  while (i < n) {
    size_t j = i + 1;
    u64 hi = u[i] >> 32;
    while (j < n && j - i < 256 && (u[j] >> 32) == hi) ++j;
    blocks.emplace_back(i, j - i);
    i = j;
  }
  put_u64le(out, u64(n));
  put_u32le(out, u32(blocks.size()));
  std::vector<u32> offs;
  for (auto& b : blocks) {
    offs.clear();
    u64 base = u[b.first];
    for (size_t k = 0; k < b.second; ++k) offs.push_back(u32(u[b.first + k] - base));
    int w = width_bits(offs.data(), offs.size());
    put_u64le(out, base);
    out.push_back(char(b.second & 0xFF)); out.push_back(char((b.second >> 8) & 0xFF));
    out.push_back(char(w));
    bitpack_into(offs.data(), offs.size(), w, out);
  }
}

// posting/pl.py encode_rollup
static void encode_rollup(const std::string& pack,
                          const std::vector<const std::string*>& posts,
                          const std::vector<u64>& splits, std::string& out) {
  out.push_back('\x00');  // KIND_ROLLUP
  put_u32le(out, u32(pack.size()));
  out += pack;
  put_u32le(out, u32(posts.size()));
  for (auto* p : posts) out += *p;
  put_u32le(out, u32(splits.size()));
  for (u64 s : splits) put_u64le(out, s);
}

// x/keys.py SplitKey: [0x03] + base_key[1:] + [start u64 BE]
static std::string split_key(const std::string& main, u64 start) {
  std::string o;
  o.push_back('\x03');
  o.append(main, 1, main.size() - 1);
  put_u64be(o, start);
  return o;
}

// ---------------------------------------------------------------------------
// Map phase
// ---------------------------------------------------------------------------

struct MapState {
  std::vector<Entry> entries;
  size_t spill_at;
  Ctx* ctx;
  std::string workdir;
  int run_no = 0;
  FILE* slow = nullptr;

  void spill() {
    if (entries.empty()) return;
    std::sort(entries.begin(), entries.end());
    char path[4096];
    snprintf(path, sizeof path, "%s/native_%04d.map", workdir.c_str(), run_no++);
    FILE* f = fopen(path, "wb");
    if (!f) { ctx->err = "cannot open run file"; return; }
    std::string buf;
    buf.reserve(1 << 22);
    for (auto& e : entries) {
      u16 kl = u16(e.key.size());
      u32 pl = u32(e.payload.size());
      char hdr[7];
      memcpy(hdr, &kl, 2); hdr[2] = char(e.kind); memcpy(hdr + 3, &pl, 4);
      buf.append(hdr, 7);
      buf += e.key;
      buf += e.payload;
      if (buf.size() > (1 << 22)) { fwrite(buf.data(), 1, buf.size(), f); buf.clear(); }
    }
    if (!buf.empty()) fwrite(buf.data(), 1, buf.size(), f);
    fclose(f);
    ctx->runs.push_back(path);
    entries.clear();
  }

  void add(std::string key, u8 kind, std::string payload) {
    entries.push_back({std::move(key), kind, std::move(payload)});
    if (entries.size() >= spill_at) spill();
  }
};

static bool resolve_ref(Ctx* c, const char* p, size_t n, u64* out) {
  if (n > 2 && p[0] == '0' && p[1] == 'x') {
    *out = strtoull(std::string(p, n).c_str(), nullptr, 16);
    return true;
  }
  bool digits = n > 0;
  for (size_t i = 0; i < n; ++i) if (p[i] < '0' || p[i] > '9') { digits = false; break; }
  if (digits) { *out = strtoull(std::string(p, n).c_str(), nullptr, 10); return true; }
  auto it = c->xids.find(std::string(p, n));
  if (it == c->xids.end()) return false;
  *out = it->second;
  return true;
}

// one fast line:  <s> <p> <o> .   |   <s> <p> "literal" .
// Returns false for anything else (or any byte >= 0x80): slow path.
static bool try_fast_line(Ctx* c, MapState* st, const char* p, size_t n,
                          u64 ns) {
  (void)ns;
  for (size_t i = 0; i < n; ++i)
    if ((u8)p[i] >= 0x80) return false;
  {
    if (p[0] != '<') return false;
    const char* se = (const char*)memchr(p + 1, '>', n - 1);
    if (!se) return false;
    size_t si = se - p;           // index of '>'
    size_t i = si + 1;
    while (i < n && p[i] == ' ') ++i;
    if (i >= n || p[i] != '<') return false;
    const char* pe = (const char*)memchr(p + i + 1, '>', n - i - 1);
    if (!pe) return false;
    size_t pstart = i + 1, pend = pe - p;
    i = pend + 1;
    while (i < n && p[i] == ' ') ++i;
    if (i >= n) return false;
    // must end with " ." / "."
    size_t e = n;
    if (p[e - 1] != '.') return false;
    --e;
    while (e > i && (p[e - 1] == ' ' || p[e - 1] == '\t')) --e;

    std::string attr(p + pstart, pend - pstart);
    auto pit = c->preds.find(attr);
    if (pit == c->preds.end()) return false;  // undeclared: Python infers
    Pred& pr = pit->second;

    u64 subj;
    if (!resolve_ref(c, p + 1, si - 1, &subj)) return false;

    if (p[i] == '<') {
      // uid edge
      const char* oe = (const char*)memchr(p + i + 1, '>', e - i - 1);
      if (!oe || size_t(oe - p) != e - 1) return false;
      u64 obj;
      if (!resolve_ref(c, p + i + 1, oe - p - i - 1, &obj)) return false;
      std::string dk = pr.data_prefix;
      put_u64be(dk, subj);
      std::string pay;
      pay.reserve(8);
      { u64 o = obj; pay.append((const char*)&o, 8); }
      st->add(std::move(dk), K_UID, std::move(pay));
      if (pr.reverse) {
        std::string rk = pr.rev_prefix;
        put_u64be(rk, obj);
        std::string pay2;
        { u64 o = subj; pay2.append((const char*)&o, 8); }
        st->add(std::move(rk), K_UID, std::move(pay2));
      }
      ++c->nquads;
      return true;
    }
    if (p[i] != '"') return false;
    // find closing quote (no escapes in the fast path)
    const char* lit = p + i + 1;
    const char* q = (const char*)memchr(lit, '"', e - i - 1);
    if (!q) return false;
    size_t ln = q - lit;
    for (size_t k = 0; k < ln; ++k)
      if (lit[k] == '\\') return false;
    size_t after = (q - p) + 1;
    if (after != e) {
      // optional ^^<dtype>: accepted only when the dtype maps to the
      // SCHEMA's own type (then text->type conversion is identical to
      // the Python parse+convert chain); anything else is slow
      if (after + 2 > e || p[after] != '^' || p[after + 1] != '^' ||
          p[after + 2] != '<' || p[e - 1] != '>')
        return false;
      std::string dt_s(p + after + 3, e - 1 - (after + 3));
      int dtid = -1;
      if (dt_s == "xs:int" || dt_s == "xs:integer" ||
          dt_s == "xs:positiveInteger" ||
          dt_s == "http://www.w3.org/2001/XMLSchema#int" ||
          dt_s == "http://www.w3.org/2001/XMLSchema#integer")
        dtid = T_INT;
      else if (dt_s == "xs:float" || dt_s == "xs:double" ||
               dt_s == "http://www.w3.org/2001/XMLSchema#float" ||
               dt_s == "http://www.w3.org/2001/XMLSchema#double")
        dtid = T_FLOAT;
      else if (dt_s == "xs:string" ||
               dt_s == "http://www.w3.org/2001/XMLSchema#string")
        dtid = T_STRING;
      else if (dt_s == "xs:boolean" ||
               dt_s == "http://www.w3.org/2001/XMLSchema#boolean")
        dtid = T_BOOL;
      else if (dt_s == "xs:dateTime" || dt_s == "xs:date" ||
               dt_s == "http://www.w3.org/2001/XMLSchema#dateTime")
        dtid = T_DATETIME;
      if (dtid < 0 || dtid != int(pr.value_type)) return false;
    }

    // convert to storage type
    u8 tid = pr.value_type;
    std::string vbytes;
    DT dt{};
    i64 iv = 0; double fv = 0; bool bv = false;
    switch (tid) {
      case T_DEFAULT: case T_STRING:
        vbytes.assign(lit, ln);
        break;
      case T_INT: {
        char* endp = nullptr;
        std::string tmp(lit, ln);
        iv = strtoll(tmp.c_str(), &endp, 10);
        if (!endp || *endp) return false;
        vbytes.append((const char*)&iv, 8);
        break;
      }
      case T_FLOAT: {
        char* endp = nullptr;
        std::string tmp(lit, ln);
        fv = strtod(tmp.c_str(), &endp);
        if (!endp || *endp) return false;
        vbytes.append((const char*)&fv, 8);
        break;
      }
      case T_BOOL: {
        if (ln == 4 && !memcmp(lit, "true", 4)) bv = true;
        else if (ln == 5 && !memcmp(lit, "false", 5)) bv = false;
        else return false;
        vbytes.push_back(bv ? '\x01' : '\x00');
        break;
      }
      case T_DATETIME: {
        if (!parse_dt(lit, ln, &dt)) return false;
        vbytes = dt_isoformat(dt);
        break;
      }
      default:
        return false;  // GEO/BIGFLOAT/VFLOAT etc.
    }

    // posting uid: VALUE_UID for single values, farmhash for list values
    u64 puid = VALUE_UID;
    if (pr.is_list) {
      std::string gb;
      switch (tid) {
        case T_INT: gb.append((const char*)&iv, 8); break;
        case T_FLOAT: gb.append((const char*)&fv, 8); break;
        case T_BOOL: gb.push_back(bv ? '\x01' : '\x00'); break;
        case T_DATETIME: gb = go_time_binary(dt); break;
        default: gb.assign(lit, ln); break;
      }
      puid = farm64((const u8*)gb.data(), gb.size());
    }
    std::string dk = pr.data_prefix;
    put_u64be(dk, subj);
    st->add(std::move(dk), K_VAL, enc_value_posting(puid, tid, vbytes));

    // index tokens
    for (u8 tok : pr.toks) {
      std::vector<std::string> terms;
      switch (tok) {
        case TOK_EXACT: terms.emplace_back(lit, ln); break;
        case TOK_INT: terms.push_back(enc_int_sortable(
            tid == T_INT ? iv : i64(fv))); break;
        case TOK_FLOAT: terms.push_back(enc_int_sortable(
            tid == T_FLOAT ? i64(fv) : iv)); break;
        case TOK_BOOL: terms.emplace_back(1, bv ? '\x01' : '\x00'); break;
        case TOK_YEAR: {
          std::string t; t.push_back(char(dt.y >> 8)); t.push_back(char(dt.y & 0xFF));
          terms.push_back(t); break;
        }
        case TOK_MONTH: {
          std::string t;
          t.push_back(char(dt.y >> 8)); t.push_back(char(dt.y & 0xFF));
          t.push_back(char(dt.mo >> 8)); t.push_back(char(dt.mo & 0xFF));
          terms.push_back(t); break;
        }
        case TOK_DAY: {
          std::string t;
          t.push_back(char(dt.y >> 8)); t.push_back(char(dt.y & 0xFF));
          t.push_back(char(dt.mo >> 8)); t.push_back(char(dt.mo & 0xFF));
          t.push_back(char(dt.d >> 8)); t.push_back(char(dt.d & 0xFF));
          terms.push_back(t); break;
        }
        case TOK_HOUR: {
          std::string t;
          t.push_back(char(dt.y >> 8)); t.push_back(char(dt.y & 0xFF));
          t.push_back(char(dt.mo >> 8)); t.push_back(char(dt.mo & 0xFF));
          t.push_back(char(dt.d >> 8)); t.push_back(char(dt.d & 0xFF));
          t.push_back(char(dt.h >> 8)); t.push_back(char(dt.h & 0xFF));
          terms.push_back(t); break;
        }
        case TOK_TERM: {
          std::set<std::string> uniq;
          for (auto& w : words_ascii(lit, ln)) uniq.insert(w);
          for (auto& w : uniq) terms.push_back(w);
          break;
        }
        case TOK_FULLTEXT: {
          std::set<std::string> uniq;
          for (auto& w : words_ascii(lit, ln))
            if (!is_stopword(w)) uniq.insert(porter_stem(w));
          for (auto& w : uniq) terms.push_back(w);
          break;
        }
        default: break;
      }
      for (auto& t : terms) {
        std::string ik = pr.idx_prefix;
        ik.push_back(char(tok));
        ik += t;
        std::string pay;
        { u64 o = subj; pay.append((const char*)&o, 8); }
        st->add(std::move(ik), K_IDX, std::move(pay));
      }
    }
    ++c->nquads;
    return true;
  }
}

static void map_line(Ctx* c, MapState* st, const char* p, size_t n, u64 ns) {
  while (n && (p[0] == ' ' || p[0] == '\t')) { ++p; --n; }
  while (n && (p[n - 1] == ' ' || p[n - 1] == '\t' || p[n - 1] == '\r')) --n;
  if (!n || p[0] == '#') return;
  if (!try_fast_line(c, st, p, n, ns) && st->slow) {
    fwrite(p, 1, n, st->slow);
    fputc('\n', st->slow);
  }
}

// ---------------------------------------------------------------------------
// SSTable writer (storage/lsm.py _SSTable.write, unencrypted form)
// ---------------------------------------------------------------------------

struct SstWriter {
  FILE* f = nullptr;
  u64 ts = 0, seq = 0, n = 0;
  std::string last_key;
  std::vector<std::pair<std::string, u64>> index;  // every 64th key
  std::vector<u64> h1s, h2s;

  bool open(const char* path) {
    f = fopen(path, "wb");
    if (f) setvbuf(f, nullptr, _IOFBF, 1 << 22);
    return f != nullptr;
  }

  void put(const std::string& key, const std::string& val) {
    if (n % 64 == 0) index.emplace_back(key, u64(ftello(f)));
    if (key != last_key) {
      u64 a, b;
      sst_bloom_hashes((const u8*)key.data(), key.size(), &a, &b);
      h1s.push_back(a);
      h2s.push_back(b);
      last_key = key;
    }
    ++seq;
    u32 kl = u32(key.size()), vl = u32(val.size());
    // _ENT = <IQQI>: key_len, ts, seq, val_len
    fwrite(&kl, 4, 1, f);
    fwrite(&ts, 8, 1, f);
    fwrite(&seq, 8, 1, f);
    fwrite(&vl, 4, 1, f);
    fwrite(key.data(), 1, kl, f);
    fwrite(val.data(), 1, vl, f);
    ++n;
  }

  void finish() {
    u64 idx_off = u64(ftello(f));
    for (auto& kv : index) {
      u32 kl = u32(kv.first.size());
      fwrite(&kl, 4, 1, f);
      fwrite(kv.first.data(), 1, kl, f);
      fwrite(&kv.second, 8, 1, f);
    }
    u64 bloom_off = u64(ftello(f));
    size_t nk = std::max<size_t>(1, h1s.size());
    u64 nbits = ((nk * 10 + 7) / 8) * 8;  // _BLOOM_BITS_PER_KEY=10
    std::vector<u8> bits(nbits / 8, 0);
    for (size_t i = 0; i < h1s.size(); ++i)
      for (int k = 0; k < SST_BLOOM_HASHES; ++k) {
        u64 b = sst_bloom_bit(h1s[i], h2s[i], k, nbits);
        bits[b >> 3] |= u8(1 << (b & 7));
      }
    fwrite(bits.data(), 1, bits.size(), f);
    // footer: [index_off u64][bloom_off u64][n u64][magic u32]
    u32 magic = 0x4C534D32;
    fwrite(&idx_off, 8, 1, f);
    fwrite(&bloom_off, 8, 1, f);
    fwrite(&n, 8, 1, f);
    fwrite(&magic, 4, 1, f);
    fflush(f);
    fclose(f);
    f = nullptr;
  }
};

// ---------------------------------------------------------------------------
// Reduce phase
// ---------------------------------------------------------------------------

struct RunReader {
  FILE* f = nullptr;
  std::string key, payload;
  u8 kind = 0;
  bool ok = false;

  bool next() {
    char hdr[7];
    if (fread(hdr, 1, 7, f) != 7) { ok = false; return false; }
    u16 kl; u32 pl;
    memcpy(&kl, hdr, 2); kind = u8(hdr[2]); memcpy(&pl, hdr + 3, 4);
    key.resize(kl); payload.resize(pl);
    if (kl && fread(&key[0], 1, kl, f) != kl) { ok = false; return false; }
    if (pl && fread(&payload[0], 1, pl, f) != pl) { ok = false; return false; }
    ok = true;
    return true;
  }
};

struct HeapCmp {
  std::vector<RunReader>* rs;
  bool operator()(int a, int b) const {
    auto& A = (*rs)[a];
    auto& B = (*rs)[b];
    if (A.key != B.key) return A.key > B.key;
    if (A.kind != B.kind) return A.kind > B.kind;
    return A.payload > B.payload;
  }
};

// kind byte of any storage key (0x00 data | 0x02 index | 0x04 reverse),
// -1 for malformed keys
static int key_kind(const std::string& k) {
  if (k.size() < 4 || k[0] != '\x00') return -1;
  u16 alen = (u8(k[1]) << 8) | u8(k[2]);
  if (k.size() < size_t(3 + alen + 1)) return -1;
  return u8(k[3 + alen]);
}

// parse attr + uid + kind back out of a data key (for count flags)
static bool parse_data_key(const std::string& k, std::string* attr, u64* uid) {
  if (k.size() < 12 || k[0] != '\x00') return false;
  u16 alen = (u8(k[1]) << 8) | u8(k[2]);
  if (k.size() < size_t(3 + alen + 1)) return false;
  u8 kind = u8(k[3 + alen]);
  if (kind != 0x00) return false;  // data
  attr->assign(k, 11, alen - 8);
  if (k.size() < size_t(3 + alen + 1 + 8)) return false;
  u64 v = 0;
  for (int i = 0; i < 8; ++i) v = (v << 8) | u8(k[3 + alen + 1 + i]);
  *uid = v;
  return true;
}

}  // namespace

// ---------------------------------------------------------------------------
// C API
// ---------------------------------------------------------------------------

extern "C" {

void* bulk_new() { return new Ctx(); }
void bulk_free(void* h) { delete (Ctx*)h; }

// scan for xid names (same over-approximation as bulk2._XID_RE):
// every <...> payload + every _:token. Returns distinct-name count.
i64 bulk_scan_xids(void* h, const char* text, i64 n) {
  Ctx* c = (Ctx*)h;
  std::set<std::string> names;
  for (i64 i = 0; i < n; ++i) {
    if (text[i] == '<') {
      i64 j = i + 1;
      while (j < n && text[j] != '>' && text[j] != '\n') ++j;
      if (j < n && text[j] == '>') {
        std::string ref(text + i + 1, j - i - 1);
        bool isuid = ref.size() > 2 && ref[0] == '0' && ref[1] == 'x';
        bool digits = !ref.empty();
        for (char ch : ref) if (ch < '0' || ch > '9') { digits = false; break; }
        if (!isuid && !digits) names.insert(std::move(ref));
        i = j;
      }
    } else if (text[i] == '_' && i + 1 < n && text[i + 1] == ':') {
      i64 j = i + 2;
      while (j < n) {
        char ch = text[j];
        bool wc = (ch >= 'a' && ch <= 'z') || (ch >= 'A' && ch <= 'Z') ||
                  (ch >= '0' && ch <= '9') || ch == '_' || ch == '.' || ch == '-';
        if (!wc) break;
        ++j;
      }
      if (j > i + 2) names.insert(std::string(text + i, j - i));
      i = j - 1;
    }
  }
  c->xid_order.assign(names.begin(), names.end());
  return i64(c->xid_order.size());
}

void bulk_set_base(void* h, u64 base) {
  Ctx* c = (Ctx*)h;
  c->base = base;
  c->xids.clear();
  c->xids.reserve(c->xid_order.size() * 2);
  for (size_t i = 0; i < c->xid_order.size(); ++i)
    c->xids[c->xid_order[i]] = base + i;
}

u64 bulk_xid_lookup(void* h, const char* name, i64 n) {
  Ctx* c = (Ctx*)h;
  auto it = c->xids.find(std::string(name, n));
  return it == c->xids.end() ? 0 : it->second;
}

void bulk_clear_preds(void* h) { ((Ctx*)h)->preds.clear(); }

// flags: 1 list | 2 reverse | 4 count | 8 lang
// toks: tokenizer identifier bytes (only ids the C++ side supports)
int bulk_add_pred(void* h, const char* name, i64 nlen, int value_type,
                  int flags, const u8* toks, i64 ntoks, u64 ns) {
  Ctx* c = (Ctx*)h;
  Pred p;
  p.value_type = u8(value_type);
  p.is_list = flags & 1;
  p.reverse = flags & 2;
  p.count = flags & 4;
  p.has_lang = flags & 8;
  p.toks.assign(toks, toks + ntoks);
  std::string attr(name, nlen);
  p.data_prefix = key_head(ns, attr, 0x00);
  p.rev_prefix = key_head(ns, attr, 0x04);
  p.idx_prefix = key_head(ns, attr, 0x02);
  c->preds[attr] = std::move(p);
  return 0;
}

// map `text` into sorted spill runs under workdir; unhandled lines are
// appended to slow_path. Returns nquads mapped natively, or -1.
i64 bulk_map(void* h, const char* text, i64 n, u64 ns,
             const char* workdir, const char* slow_path, i64 spill_entries) {
  Ctx* c = (Ctx*)h;
  MapState st;
  st.ctx = c;
  st.workdir = workdir;
  st.spill_at = size_t(spill_entries);
  st.slow = fopen(slow_path, "wb");
  if (!st.slow) return -1;
  u64 before = c->nquads;
  i64 i = 0;
  while (i < n) {
    i64 j = i;
    while (j < n && text[j] != '\n') ++j;
    map_line(c, &st, text + i, j - i, ns);
    i = j + 1;
  }
  st.spill();
  fclose(st.slow);
  if (!c->err.empty()) return -1;
  return i64(c->nquads - before);
}

i64 bulk_run_count(void* h) { return i64(((Ctx*)h)->runs.size()); }
i64 bulk_run_path(void* h, i64 i, char* out, i64 cap) {
  Ctx* c = (Ctx*)h;
  if (i < 0 || size_t(i) >= c->runs.size()) return -1;
  i64 l = i64(c->runs[i].size());
  if (l >= cap) return -1;
  memcpy(out, c->runs[i].c_str(), l + 1);
  return l;
}

// merge `paths` (newline-joined run files, native + python-produced) and
// emit the final record stream: [u16 klen][key][u32 rlen][record] into
// out_main; CountKey records into out_counts. Returns record count, -1
// on error.
// sst=0: out_main is a [u16 klen][key][u32 rlen][rec] stream.
// sst=1: out_main is a finished SSTable (storage/lsm.py _SSTable
//        layout, unencrypted) with version `ts` and seqs from seq_base+1.
// out_stats (may be null/empty): index-key selectivity records
// [u16 klen][key][u64 uid_count], one per index key — the StatsHolder
// feed the Python slow path emits inline; without it eq plans fall back
// to default selectivities until the first commits.
i64 bulk_reduce(void* h, const char* paths_joined, i64 plen,
                u64 max_part_uids, const char* out_main,
                const char* out_counts, const char* out_stats, u64 ns,
                i64 sst, u64 ts, u64 seq_base) {
  Ctx* c = (Ctx*)h;
  std::vector<std::string> paths;
  {
    std::string all(paths_joined, plen);
    size_t pos = 0;
    while (pos < all.size()) {
      size_t nl = all.find('\n', pos);
      if (nl == std::string::npos) nl = all.size();
      if (nl > pos) paths.emplace_back(all, pos, nl - pos);
      pos = nl + 1;
    }
  }
  std::vector<RunReader> rs(paths.size());
  std::priority_queue<int, std::vector<int>, HeapCmp> heap{HeapCmp{&rs}};
  for (size_t i = 0; i < paths.size(); ++i) {
    rs[i].f = fopen(paths[i].c_str(), "rb");
    if (!rs[i].f) return -1;
    setvbuf(rs[i].f, nullptr, _IOFBF, 1 << 20);
    if (rs[i].next()) heap.push(int(i));
  }
  FILE* fm = nullptr;
  SstWriter sw;
  if (sst) {
    sw.ts = ts;
    sw.seq = seq_base;
    if (!sw.open(out_main)) return -1;
  } else {
    fm = fopen(out_main, "wb");
    if (!fm) return -1;
    setvbuf(fm, nullptr, _IOFBF, 1 << 22);
  }
  FILE* fs = nullptr;
  if (out_stats && out_stats[0]) {
    // stats are advisory (the Python reader tolerates a missing file):
    // an open failure must not fail the reduce itself
    fs = fopen(out_stats, "wb");
    if (fs) setvbuf(fs, nullptr, _IOFBF, 1 << 20);
  }

  // (attr, count) -> uids, for @count predicates
  std::map<std::pair<std::string, u64>, std::vector<u64>> counts;
  // split-part records live in the 0x03 key region, AFTER every data
  // key — they go into the second (sorted) batch, keeping the main
  // stream in ascending key order for ingest_sorted
  std::vector<std::pair<std::string, std::string>> extra;

  i64 nrecords = 0;
  std::string cur_key;
  std::vector<u64> uids;
  std::map<u64, std::string> posts;  // posting uid -> wire bytes (last wins)
  bool have = false;

  auto emit_group = [&]() {
    if (!have) return;
    std::sort(uids.begin(), uids.end());
    uids.erase(std::unique(uids.begin(), uids.end()), uids.end());

    std::string attr;
    u64 subj = 0;
    bool is_data = parse_data_key(cur_key, &attr, &subj);
    if (is_data && !uids.empty()) {
      auto pit = c->preds.find(attr);
      if (pit != c->preds.end() && pit->second.count)
        counts[{attr, u64(uids.size())}].push_back(subj);
    }
    if (fs && !uids.empty() && cur_key.size() <= 0xFFFF &&
        key_kind(cur_key) == 0x02) {
      // index key: emit its (key, posting-count) selectivity record;
      // oversized keys are skipped — a truncated u16 klen would corrupt
      // every later record in the stream
      u16 kl = u16(cur_key.size());
      u64 n = u64(uids.size());
      fwrite(&kl, 2, 1, fs);
      fwrite(cur_key.data(), 1, kl, fs);
      fwrite(&n, 8, 1, fs);
    }

    auto write_rec = [&](const std::string& key, const std::string& rec) {
      if (sst) {
        sw.put(key, rec);
      } else {
        u16 kl = u16(key.size());
        u32 rl = u32(rec.size());
        fwrite(&kl, 2, 1, fm);
        fwrite(key.data(), 1, kl, fm);
        fwrite(&rl, 4, 1, fm);
        fwrite(rec.data(), 1, rl, fm);
      }
      ++nrecords;
    };

    std::vector<const std::string*> ordered;
    for (auto& kv : posts) ordered.push_back(&kv.second);

    if (!posts.empty() || uids.size() <= max_part_uids) {
      std::string pack, rec;
      serialize_uids(uids, pack);
      encode_rollup(pack, ordered, {}, rec);
      write_rec(cur_key, rec);
    } else {
      // multi-part split (posting/pl.py rollup_writes)
      u64 per = max_part_uids / 2;
      if (per < 1) per = 1;
      std::vector<u64> starts;
      for (size_t i = 0; i < uids.size(); i += per) {
        size_t cnt = std::min(size_t(per), uids.size() - i);
        std::vector<u64> chunk(uids.begin() + i, uids.begin() + i + cnt);
        starts.push_back(chunk[0]);
        std::string pack, rec;
        serialize_uids(chunk, pack);
        encode_rollup(pack, {}, {}, rec);
        extra.emplace_back(split_key(cur_key, chunk[0]), std::move(rec));
      }
      std::string pack, rec;
      serialize_uids({}, pack);
      encode_rollup(pack, {}, starts, rec);
      write_rec(cur_key, rec);
    }
    uids.clear();
    posts.clear();
  };

  while (!heap.empty()) {
    int i = heap.top();
    heap.pop();
    RunReader& r = rs[i];
    if (!have || r.key != cur_key) {
      emit_group();
      cur_key = r.key;
      have = true;
    }
    if (r.kind == K_VAL) {
      if (r.payload.size() >= 9) {
        u64 puid;
        memcpy(&puid, r.payload.data() + 1, 8);
        posts[puid] = r.payload;
      }
    } else if (r.payload.size() == 8) {
      u64 u;
      memcpy(&u, r.payload.data(), 8);
      uids.push_back(u);
    }
    if (r.next()) heap.push(i);
  }
  emit_group();
  if (sst) sw.finish();
  else fclose(fm);
  if (fs) fclose(fs);
  for (auto& r : rs) if (r.f) fclose(r.f);

  FILE* fc = fopen(out_counts, "wb");
  if (!fc) return -1;
  std::vector<std::pair<std::string, std::string>> crecs;
  for (auto& kv : counts) {
    // CountKey: head + [count u32 BE][rev u8]
    std::string key = key_head(ns, kv.first.first, 0x08);
    u32 cnt = u32(kv.first.second);
    key.push_back(char((cnt >> 24) & 0xFF)); key.push_back(char((cnt >> 16) & 0xFF));
    key.push_back(char((cnt >> 8) & 0xFF)); key.push_back(char(cnt & 0xFF));
    key.push_back('\x00');
    std::vector<u64> us = kv.second;
    std::sort(us.begin(), us.end());
    us.erase(std::unique(us.begin(), us.end()), us.end());
    std::string pack, rec;
    serialize_uids(us, pack);
    encode_rollup(pack, {}, {}, rec);
    crecs.emplace_back(std::move(key), std::move(rec));
  }
  for (auto& kr : extra) crecs.emplace_back(std::move(kr));
  // byte order, not (attr,count) order: ingest_sorted needs key order
  std::sort(crecs.begin(), crecs.end());
  for (auto& kr : crecs) {
    u16 kl = u16(kr.first.size());
    u32 rl = u32(kr.second.size());
    fwrite(&kl, 2, 1, fc);
    fwrite(kr.first.data(), 1, kl, fc);
    fwrite(&rl, 4, 1, fc);
    fwrite(kr.second.data(), 1, rl, fc);
  }
  fclose(fc);
  return nrecords;
}

}  // extern "C"
