#include "tpudf/orc_reader.hpp"

#include <zlib.h>

#include <algorithm>
#include <cstring>
#include <stdexcept>
#include <utility>

#include "tpudf/parquet_reader.hpp"  // snappy_uncompress
#include "tpudf/protobuf_wire.hpp"

namespace tpudf {
namespace orc {

namespace {

using pb::Message;

[[noreturn]] void fail(std::string const& msg) {
  throw std::runtime_error("orc read: " + msg);
}

// ---- orc_proto.proto field numbers ----------------------------------------

// PostScript
constexpr uint32_t kPsFooterLength = 1;
constexpr uint32_t kPsCompression = 2;
constexpr uint32_t kPsMagic = 8000;
// Footer
constexpr uint32_t kFtStripes = 3;
constexpr uint32_t kFtTypes = 4;
constexpr uint32_t kFtNumRows = 6;
// StripeInformation
constexpr uint32_t kSiOffset = 1;
constexpr uint32_t kSiIndexLength = 2;
constexpr uint32_t kSiDataLength = 3;
constexpr uint32_t kSiFooterLength = 4;
constexpr uint32_t kSiNumRows = 5;
// Type
constexpr uint32_t kTyKind = 1;
constexpr uint32_t kTySubtypes = 2;
constexpr uint32_t kTyFieldNames = 3;
constexpr uint32_t kTyPrecision = 5;
constexpr uint32_t kTyScale = 6;
// StripeFooter
constexpr uint32_t kSfStreams = 1;
constexpr uint32_t kSfColumns = 2;
constexpr uint32_t kSfWriterTimezone = 3;
// Stream
constexpr uint32_t kStKind = 1;
constexpr uint32_t kStColumn = 2;
constexpr uint32_t kStLength = 3;
// ColumnEncoding
constexpr uint32_t kCeKind = 1;
constexpr uint32_t kCeDictSize = 2;

// Stream kinds
constexpr uint64_t kStreamPresent = 0;
constexpr uint64_t kStreamData = 1;
constexpr uint64_t kStreamLength = 2;
constexpr uint64_t kStreamDictData = 3;
constexpr uint64_t kStreamSecondary = 5;

// compression kinds
constexpr uint64_t kCompNone = 0;
constexpr uint64_t kCompZlib = 1;
constexpr uint64_t kCompSnappy = 2;

// encoding kinds
constexpr uint64_t kEncDirect = 0;
constexpr uint64_t kEncDictionary = 1;
constexpr uint64_t kEncDirectV2 = 2;
constexpr uint64_t kEncDictionaryV2 = 3;

// ---- compression (ORC chunk framing) --------------------------------------

std::vector<uint8_t> zlib_raw_inflate(uint8_t const* in, uint64_t n) {
  // ORC ZLIB chunks are raw deflate (no zlib/gzip header)
  std::vector<uint8_t> out;
  out.resize(std::max<uint64_t>(n * 4, 4096));
  z_stream zs;
  std::memset(&zs, 0, sizeof(zs));
  if (inflateInit2(&zs, -MAX_WBITS) != Z_OK) fail("zlib init failed");
  zs.next_in = const_cast<Bytef*>(in);
  zs.avail_in = static_cast<uInt>(n);
  size_t written = 0;
  int rc = Z_OK;
  do {
    if (written == out.size()) out.resize(out.size() * 2);
    zs.next_out = out.data() + written;
    zs.avail_out = static_cast<uInt>(out.size() - written);
    rc = inflate(&zs, Z_NO_FLUSH);
    written = zs.total_out;
    if (rc == Z_STREAM_END) break;
    if (rc != Z_OK && rc != Z_BUF_ERROR) {
      inflateEnd(&zs);
      fail("zlib inflate failed");
    }
  } while (zs.avail_in > 0 || rc == Z_BUF_ERROR);
  inflateEnd(&zs);
  out.resize(written);
  return out;
}

// Undo the ORC chunked compression framing for one stream.
std::vector<uint8_t> decode_stream(uint8_t const* p, uint64_t n,
                                   uint64_t compression) {
  if (compression == kCompNone) return std::vector<uint8_t>(p, p + n);
  std::vector<uint8_t> out;
  uint64_t pos = 0;
  while (pos < n) {
    if (pos + 3 > n) fail("truncated compression chunk header");
    uint32_t h = static_cast<uint32_t>(p[pos]) |
                 (static_cast<uint32_t>(p[pos + 1]) << 8) |
                 (static_cast<uint32_t>(p[pos + 2]) << 16);
    pos += 3;
    bool const original = h & 1;
    uint64_t const chunk = h >> 1;
    if (pos + chunk > n) fail("compression chunk past stream end");
    if (original) {
      out.insert(out.end(), p + pos, p + pos + chunk);
    } else if (compression == kCompZlib) {
      auto d = zlib_raw_inflate(p + pos, chunk);
      out.insert(out.end(), d.begin(), d.end());
    } else if (compression == kCompSnappy) {
      // ORC does not declare an uncompressed chunk size anywhere else; the
      // snappy stream's own varint preamble is authoritative.
      auto d = parquet::snappy_uncompress(p + pos, chunk,
                                          parquet::kSnappyNoExpectedSize);
      out.insert(out.end(), d.begin(), d.end());
    } else {
      fail("unsupported compression kind " + std::to_string(compression));
    }
    pos += chunk;
  }
  return out;
}

// ---- primitive decoders ---------------------------------------------------

struct Cursor {
  uint8_t const* p;
  uint64_t len;
  uint64_t pos = 0;

  uint8_t byte() {
    if (pos >= len) fail("stream underrun");
    return p[pos++];
  }

  uint64_t varint_u() {
    uint64_t out = 0;
    int shift = 0;
    while (shift <= 63) {
      uint8_t b = byte();
      out |= static_cast<uint64_t>(b & 0x7F) << shift;
      if (!(b & 0x80)) return out;
      shift += 7;
    }
    fail("bad varint");
  }

  int64_t varint_s() {  // zigzag
    uint64_t u = varint_u();
    return static_cast<int64_t>((u >> 1) ^ (~(u & 1) + 1));
  }

  // 128-bit zigzag varint (ORC DECIMAL with precision > 18): returns
  // (lo unsigned, hi signed) little-endian limbs of the two's-complement
  // value — the framework's DECIMAL128 storage layout.
  std::pair<uint64_t, int64_t> varint_s128() {
    uint64_t lo = 0, hi = 0;
    int shift = 0;
    while (shift <= 127) {
      uint8_t b = byte();
      uint64_t g = b & 0x7F;
      if (shift < 64) {
        lo |= g << shift;
        if (shift + 7 > 64) hi |= g >> (64 - shift);
      } else {
        hi |= g << (shift - 64);
      }
      if (!(b & 0x80)) {
        // the 19th byte contributes only 2 bits (shift 126): payload above
        // them means a corrupt stream, not a silently-truncated value
        if (shift == 126 && (g >> 2) != 0) fail("varint128 high-bit garbage");
        break;
      }
      shift += 7;
      if (shift > 127) fail("varint128 overruns 128 bits");
    }
    uint64_t sign = lo & 1;
    uint64_t rlo = (lo >> 1) | (hi << 63);
    uint64_t rhi = hi >> 1;
    if (sign) {
      rlo = ~rlo;
      rhi = ~rhi;
    }
    return {rlo, static_cast<int64_t>(rhi)};
  }
};

// Byte RLE: control c in [0,127] -> run of c+3 copies of next byte;
// c in [128,255] -> 256-c literal bytes.
std::vector<uint8_t> decode_byte_rle(std::vector<uint8_t> const& s,
                                     int64_t count) {
  std::vector<uint8_t> out;
  out.reserve(count);
  Cursor c{s.data(), s.size()};
  while (static_cast<int64_t>(out.size()) < count) {
    uint8_t ctrl = c.byte();
    if (ctrl < 128) {
      uint8_t v = c.byte();
      out.insert(out.end(), ctrl + 3, v);
    } else {
      int n = 256 - ctrl;
      for (int k = 0; k < n; ++k) out.push_back(c.byte());
    }
  }
  out.resize(count);
  return out;
}

// Boolean RLE: byte RLE over bit-packed bytes, MSB first.
std::vector<uint8_t> decode_bool_rle(std::vector<uint8_t> const& s,
                                     int64_t count) {
  auto bytes = decode_byte_rle(s, (count + 7) / 8);
  std::vector<uint8_t> out(count);
  for (int64_t i = 0; i < count; ++i) {
    out[i] = (bytes[i / 8] >> (7 - (i % 8))) & 1;
  }
  return out;
}

// Int RLEv1: control c in [0,127] -> run of c+3 with signed delta byte and
// varint base; c in [128,255] -> 256-c literal varints.
std::vector<int64_t> decode_rle_v1(std::vector<uint8_t> const& s,
                                   int64_t count, bool is_signed) {
  std::vector<int64_t> out;
  out.reserve(count);
  Cursor c{s.data(), s.size()};
  while (static_cast<int64_t>(out.size()) < count) {
    uint8_t ctrl = c.byte();
    if (ctrl < 128) {
      int run = ctrl + 3;
      int8_t delta = static_cast<int8_t>(c.byte());
      int64_t v = is_signed ? c.varint_s()
                            : static_cast<int64_t>(c.varint_u());
      for (int k = 0; k < run; ++k) out.push_back(v + k * delta);
    } else {
      int n = 256 - ctrl;
      for (int k = 0; k < n; ++k) {
        out.push_back(is_signed ? c.varint_s()
                                : static_cast<int64_t>(c.varint_u()));
      }
    }
  }
  out.resize(count);
  return out;
}

// Round a bit count up to the nearest width the RLEv2 table can encode —
// writers pack patch-list entries at getClosestFixedBits(pgw + pw), not at
// the raw sum (e.g. 25 combined bits are packed at 26).
int closest_fixed_bits(int n) {
  if (n <= 24) return n < 1 ? 1 : n;
  if (n <= 26) return 26;
  if (n <= 28) return 28;
  if (n <= 30) return 30;
  if (n <= 32) return 32;
  if (n <= 40) return 40;
  if (n <= 48) return 48;
  if (n <= 56) return 56;
  return 64;
}

// RLEv2 encoded-width table (5-bit codes).
int rle2_width(int code, bool delta_mode) {
  if (code == 0) return delta_mode ? 0 : 1;
  if (code <= 23) return code + 1;
  switch (code) {
    case 24: return 26;
    case 25: return 28;
    case 26: return 30;
    case 27: return 32;
    case 28: return 40;
    case 29: return 48;
    case 30: return 56;
    case 31: return 64;
  }
  fail("bad rle2 width code");
}

// Big-endian bit unpacking, `width` bits per value.
uint64_t read_bits(uint8_t const* p, uint64_t nbytes, uint64_t bit_pos,
                   int width) {
  uint64_t out = 0;
  for (int k = 0; k < width; ++k) {
    uint64_t bit = bit_pos + k;
    uint64_t byte = bit >> 3;
    if (byte >= nbytes) fail("bit-packed run past stream end");
    out = (out << 1) | ((p[byte] >> (7 - (bit & 7))) & 1);
  }
  return out;
}

int64_t unzigzag(uint64_t u) {
  return static_cast<int64_t>((u >> 1) ^ (~(u & 1) + 1));
}

}  // namespace

std::vector<int64_t> decode_rle_v2(uint8_t const* data, uint64_t len,
                                   int64_t count, bool is_signed) {
  std::vector<int64_t> out;
  out.reserve(count);
  Cursor c{data, len};
  while (static_cast<int64_t>(out.size()) < count) {
    uint8_t first = c.byte();
    int mode = first >> 6;
    if (mode == 0) {
      // short repeat: width (bytes) in bits 5-3, count-3 in bits 2-0
      int w = ((first >> 3) & 7) + 1;
      int n = (first & 7) + 3;
      uint64_t v = 0;
      for (int k = 0; k < w; ++k) v = (v << 8) | c.byte();
      int64_t sv = is_signed ? unzigzag(v) : static_cast<int64_t>(v);
      out.insert(out.end(), n, sv);
    } else if (mode == 1) {
      // direct: 5-bit width code, 9-bit length-1
      int w = rle2_width((first >> 1) & 0x1F, false);
      int n = ((first & 1) << 8 | c.byte()) + 1;
      uint64_t nbits = static_cast<uint64_t>(n) * w;
      uint64_t nbytes = (nbits + 7) / 8;
      if (c.pos + nbytes > c.len) fail("rle2 direct run past end");
      for (int k = 0; k < n; ++k) {
        uint64_t v = read_bits(c.p + c.pos, nbytes,
                               static_cast<uint64_t>(k) * w, w);
        out.push_back(is_signed ? unzigzag(v) : static_cast<int64_t>(v));
      }
      c.pos += nbytes;
    } else if (mode == 3) {
      // delta: base varint, delta-base signed varint, packed delta
      // magnitudes at width W (W==0 -> fixed delta)
      int w = rle2_width((first >> 1) & 0x1F, true);
      int n = ((first & 1) << 8 | c.byte()) + 1;
      int64_t base = is_signed ? c.varint_s()
                               : static_cast<int64_t>(c.varint_u());
      int64_t delta_base = c.varint_s();
      out.push_back(base);
      if (n > 1) out.push_back(base + delta_base);
      int64_t prev = base + delta_base;
      int remaining = n - 2;
      int64_t sign = delta_base < 0 ? -1 : 1;
      if (w == 0) {
        for (int k = 0; k < remaining; ++k) {
          prev += delta_base;
          out.push_back(prev);
        }
      } else {
        uint64_t nbits = static_cast<uint64_t>(remaining) * w;
        uint64_t nbytes = (nbits + 7) / 8;
        if (c.pos + nbytes > c.len) fail("rle2 delta run past end");
        for (int k = 0; k < remaining; ++k) {
          uint64_t d = read_bits(c.p + c.pos, nbytes,
                                 static_cast<uint64_t>(k) * w, w);
          prev += sign * static_cast<int64_t>(d);
          out.push_back(prev);
        }
        c.pos += nbytes;
      }
    } else {
      // patched base
      int w = rle2_width((first >> 1) & 0x1F, false);
      int n = ((first & 1) << 8 | c.byte()) + 1;
      uint8_t third = c.byte();
      int bw = ((third >> 5) & 7) + 1;            // base width, bytes
      int pw = rle2_width(third & 0x1F, false);   // patch width, bits
      uint8_t fourth = c.byte();
      int pgw = ((fourth >> 5) & 7) + 1;          // patch gap width, bits
      int pl = fourth & 0x1F;                     // patch list length
      // base: big-endian, MSB of the bw-byte field is the sign bit
      uint64_t raw_base = 0;
      for (int k = 0; k < bw; ++k) raw_base = (raw_base << 8) | c.byte();
      uint64_t sign_mask = 1ull << (bw * 8 - 1);
      int64_t base = (raw_base & sign_mask)
                         ? -static_cast<int64_t>(raw_base & (sign_mask - 1))
                         : static_cast<int64_t>(raw_base);
      uint64_t nbits = static_cast<uint64_t>(n) * w;
      uint64_t nbytes = (nbits + 7) / 8;
      if (c.pos + nbytes > c.len) fail("rle2 patched run past end");
      std::vector<uint64_t> vals(n);
      for (int k = 0; k < n; ++k) {
        vals[k] = read_bits(c.p + c.pos, nbytes,
                            static_cast<uint64_t>(k) * w, w);
      }
      c.pos += nbytes;
      int pbits = closest_fixed_bits(pgw + pw);
      uint64_t pnbits = static_cast<uint64_t>(pl) * pbits;
      uint64_t pnbytes = (pnbits + 7) / 8;
      if (c.pos + pnbytes > c.len) fail("rle2 patch list past end");
      uint64_t idx = 0;
      for (int k = 0; k < pl; ++k) {
        uint64_t entry = read_bits(c.p + c.pos, pnbytes,
                                   static_cast<uint64_t>(k) * pbits, pbits);
        uint64_t gap = entry >> pw;
        uint64_t patch = entry & ((pw == 64) ? ~0ull : ((1ull << pw) - 1));
        idx += gap;
        if (idx >= static_cast<uint64_t>(n)) fail("rle2 patch index oob");
        vals[idx] |= patch << w;
      }
      c.pos += pnbytes;
      for (int k = 0; k < n; ++k) {
        out.push_back(base + static_cast<int64_t>(vals[k]));
      }
    }
  }
  out.resize(count);
  return out;
}

namespace {

std::vector<int64_t> decode_int_stream(std::vector<uint8_t> const& s,
                                       int64_t count, bool is_signed,
                                       bool v2) {
  if (v2) return decode_rle_v2(s.data(), s.size(), count, is_signed);
  return decode_rle_v1(s, count, is_signed);
}

// ---- file structure -------------------------------------------------------

struct TypeInfo {
  int32_t kind = 0;
  int32_t precision = 0;
  int32_t scale = 0;
  std::string name;
};

struct FileMeta {
  uint64_t compression = kCompNone;
  int64_t num_rows = 0;
  std::vector<TypeInfo> leaves;   // flat struct children; leaf i = column id i+1
  std::vector<Message> stripes;   // StripeInformation messages (parsed)
  std::vector<std::string> stripe_bufs;  // backing bytes for `stripes`
};

FileMeta parse_meta(uint8_t const* file, uint64_t len) {
  if (len < 4 || std::memcmp(file, "ORC", 3) != 0) {
    fail("not an ORC file (missing magic)");
  }
  uint8_t ps_len = file[len - 1];
  if (1ull + ps_len > len) fail("bad postscript length");
  Message ps = Message::parse(file + len - 1 - ps_len, ps_len);
  if (ps.bytes(kPsMagic) != "ORC") fail("postscript magic mismatch");
  FileMeta meta;
  meta.compression = ps.u64(kPsCompression, kCompNone);
  uint64_t footer_len = ps.u64(kPsFooterLength);
  // subtraction form: footer_len is an attacker-controlled varint and the
  // additive check would wrap in uint64
  if (footer_len > len - 1 - ps_len) fail("footer length out of bounds");
  uint64_t footer_off = len - 1 - ps_len - footer_len;
  auto footer_bytes =
      decode_stream(file + footer_off, footer_len, meta.compression);
  Message footer = Message::parse(footer_bytes.data(), footer_bytes.size());
  meta.num_rows = static_cast<int64_t>(footer.u64(kFtNumRows));

  auto type_fields = footer.fields(kFtTypes);
  if (type_fields.empty()) fail("missing types");
  Message root = Message::parse(
      reinterpret_cast<uint8_t const*>(type_fields[0]->bytes.data()),
      type_fields[0]->bytes.size());
  if (root.u64(kTyKind) != static_cast<uint64_t>(Kind::STRUCT)) {
    fail("root type must be a struct");
  }
  auto names = root.fields(kTyFieldNames);
  auto subtypes = root.fields(kTySubtypes);
  if (subtypes.size() != type_fields.size() - 1) {
    fail("nested ORC schemas are not supported yet (flat columns only)");
  }
  for (uint64_t i = 1; i < type_fields.size(); ++i) {
    Message ty = Message::parse(
        reinterpret_cast<uint8_t const*>(type_fields[i]->bytes.data()),
        type_fields[i]->bytes.size());
    TypeInfo info;
    info.kind = static_cast<int32_t>(ty.u64(kTyKind));
    info.precision = static_cast<int32_t>(ty.u64(kTyPrecision));
    info.scale = static_cast<int32_t>(ty.u64(kTyScale));
    if (i - 1 < names.size()) info.name = std::string(names[i - 1]->bytes);
    if (ty.field(kTySubtypes) != nullptr) {
      fail("nested ORC schemas are not supported yet (flat columns only)");
    }
    meta.leaves.push_back(std::move(info));
  }
  for (auto const* f : footer.fields(kFtStripes)) {
    meta.stripe_bufs.emplace_back(f->bytes);
  }
  // the footer states the row count twice; a stripe list that lost an
  // entry (or a stripe whose count changed) would otherwise decode to a
  // shorter table of the right schema
  uint64_t stripe_rows = 0;
  for (auto const& buf : meta.stripe_bufs) {
    meta.stripes.push_back(Message::parse(
        reinterpret_cast<uint8_t const*>(buf.data()), buf.size()));
    stripe_rows += meta.stripes.back().u64(kSiNumRows);
  }
  if (stripe_rows != footer.u64(kFtNumRows)) {
    fail("footer numberOfRows disagrees with the sum over its stripes");
  }
  return meta;
}

struct StreamEntry {
  uint64_t kind = 0;
  uint64_t col = 0;
  uint64_t offset = 0;  // absolute file offset
  uint64_t length = 0;
};

struct StripeDirectory {
  std::vector<StreamEntry> streams;
  std::vector<uint64_t> encodings;   // ColumnEncoding.kind per column id
  std::vector<uint64_t> dict_sizes;  // ColumnEncoding.dictionarySize
  std::string writer_timezone;       // StripeFooter.writerTimezone
};

// Parse the stripe footer's stream directory ONCE per stripe. The streams
// are laid out back to back from the stripe's start — index-region streams
// (ROW_INDEX etc.) first, inside indexLength, then the data streams — so
// the cursor starts at the stripe offset and walks EVERY listed stream.
StripeDirectory parse_directory(uint64_t file_len, Message const& stripe,
                                Message const& sf) {
  StripeDirectory dir;
  uint64_t pos = stripe.u64(kSiOffset);
  for (auto const* f : sf.fields(kSfStreams)) {
    Message st = Message::parse(
        reinterpret_cast<uint8_t const*>(f->bytes.data()), f->bytes.size());
    StreamEntry e;
    e.kind = st.u64(kStKind);
    e.col = st.u64(kStColumn);
    e.length = st.u64(kStLength);
    e.offset = pos;
    // overflow-safe bounds check (lengths are attacker-controlled varints)
    if (e.offset > file_len || e.length > file_len - e.offset) {
      fail("stream extends past end of file");
    }
    dir.streams.push_back(e);
    pos += e.length;
  }
  for (auto const* f : sf.fields(kSfColumns)) {
    Message enc = Message::parse(
        reinterpret_cast<uint8_t const*>(f->bytes.data()), f->bytes.size());
    dir.encodings.push_back(enc.u64(kCeKind));
    dir.dict_sizes.push_back(enc.u64(kCeDictSize));
  }
  dir.writer_timezone = std::string(sf.bytes(kSfWriterTimezone));
  return dir;
}

struct ColumnStreams {
  std::vector<uint8_t> present, data, length, dict, secondary;
  bool has_present = false;
  uint64_t encoding = kEncDirect;
  uint64_t dict_size = 0;
};

// Slice + un-frame the streams that belong to `col` (1-based; 0 = root).
ColumnStreams gather_streams(uint8_t const* file, FileMeta const& meta,
                             StripeDirectory const& dir, uint64_t col) {
  ColumnStreams out;
  for (auto const& e : dir.streams) {
    if (e.col != col) continue;
    if (e.kind != kStreamPresent && e.kind != kStreamData &&
        e.kind != kStreamLength && e.kind != kStreamDictData &&
        e.kind != kStreamSecondary) {
      continue;  // row indexes, bloom filters, ...
    }
    auto decoded = decode_stream(file + e.offset, e.length, meta.compression);
    if (e.kind == kStreamPresent) {
      out.present = std::move(decoded);
      out.has_present = true;
    } else if (e.kind == kStreamData) {
      out.data = std::move(decoded);
    } else if (e.kind == kStreamLength) {
      out.length = std::move(decoded);
    } else if (e.kind == kStreamDictData) {
      out.dict = std::move(decoded);
    } else {
      out.secondary = std::move(decoded);
    }
  }
  // one ColumnEncoding per column id (root included): a column without
  // one must not be read as DIRECT by default
  if (col >= dir.encodings.size()) {
    fail("stripe footer has no encoding for the column");
  }
  out.encoding = dir.encodings[col];
  out.dict_size = dir.dict_sizes[col];
  return out;
}

void decode_stripe_column(uint8_t const* file, FileMeta const& meta,
                          StripeDirectory const& dir, int32_t leaf,
                          int64_t stripe_rows, OrcColumn& out) {
  auto const& ty = meta.leaves[leaf];
  ColumnStreams s =
      gather_streams(file, meta, dir, static_cast<uint64_t>(leaf) + 1);

  std::vector<uint8_t> valid(stripe_rows, 1);
  int64_t n_present = stripe_rows;
  if (s.has_present) {
    valid = decode_bool_rle(s.present, stripe_rows);
    n_present = 0;
    for (uint8_t v : valid) n_present += v;
  }
  bool const v2 =
      s.encoding == kEncDirectV2 || s.encoding == kEncDictionaryV2;
  bool const dict_enc =
      s.encoding == kEncDictionary || s.encoding == kEncDictionaryV2;

  auto scatter_i64 = [&](std::vector<int64_t> const& vals) {
    int64_t next = 0;
    for (int64_t r = 0; r < stripe_rows; ++r) {
      out.data.push_back(valid[r] ? vals[next++] : 0);
    }
  };

  switch (static_cast<Kind>(ty.kind)) {
    case Kind::BOOLEAN: {
      auto bits = decode_bool_rle(s.data, n_present);
      std::vector<int64_t> vals(bits.begin(), bits.end());
      scatter_i64(vals);
      break;
    }
    case Kind::BYTE: {
      auto bytes = decode_byte_rle(s.data, n_present);
      std::vector<int64_t> vals;
      vals.reserve(n_present);
      for (uint8_t b : bytes) vals.push_back(static_cast<int8_t>(b));
      scatter_i64(vals);
      break;
    }
    case Kind::SHORT:
    case Kind::INT:
    case Kind::LONG:
    case Kind::DATE:
      scatter_i64(decode_int_stream(s.data, n_present, true, v2));
      break;
    case Kind::FLOAT: {
      if (s.data.size() < static_cast<uint64_t>(n_present) * 4) {
        fail("float stream underrun");
      }
      std::vector<int64_t> vals;
      vals.reserve(n_present);
      for (int64_t k = 0; k < n_present; ++k) {
        uint32_t bits;
        std::memcpy(&bits, s.data.data() + k * 4, 4);
        vals.push_back(static_cast<int64_t>(bits));
      }
      scatter_i64(vals);
      break;
    }
    case Kind::DOUBLE: {
      if (s.data.size() < static_cast<uint64_t>(n_present) * 8) {
        fail("double stream underrun");
      }
      std::vector<int64_t> vals;
      vals.reserve(n_present);
      for (int64_t k = 0; k < n_present; ++k) {
        uint64_t bits;
        std::memcpy(&bits, s.data.data() + k * 8, 8);
        vals.push_back(static_cast<int64_t>(bits));
      }
      scatter_i64(vals);
      break;
    }
    case Kind::DECIMAL: {
      // unbounded base-128 zigzag varints + scale stream (ignored: the
      // footer scale is authoritative for modern writers)
      Cursor c{s.data.data(), s.data.size()};
      if (ty.precision > 18) {
        // precision 19-38 -> DECIMAL128 limb pairs, two i64 per row
        std::vector<std::pair<uint64_t, int64_t>> vals;
        vals.reserve(n_present);
        for (int64_t k = 0; k < n_present; ++k) {
          vals.push_back(c.varint_s128());
        }
        int64_t next = 0;
        for (int64_t r = 0; r < stripe_rows; ++r) {
          if (valid[r]) {
            out.data.push_back(static_cast<int64_t>(vals[next].first));
            out.data.push_back(vals[next].second);
            ++next;
          } else {
            out.data.push_back(0);
            out.data.push_back(0);
          }
        }
        break;
      }
      std::vector<int64_t> vals;
      vals.reserve(n_present);
      for (int64_t k = 0; k < n_present; ++k) vals.push_back(c.varint_s());
      scatter_i64(vals);
      break;
    }
    case Kind::TIMESTAMP: {
      // data = signed seconds from 2015-01-01 in the WRITER's timezone
      // (stripe footer writerTimezone). Two wire conventions exist for
      // pre-1970 fractional values, both truncating seconds toward zero:
      //   * ORC C++ / pyarrow emit SIGNED nanos with the same sign as the
      //     value (observed on the wire: -1.5s -> secs=-1, nanos=-5e8) —
      //     plain signed addition reconstructs exactly;
      //   * orc-java's TimestampTreeReader convention keeps nanos
      //     POSITIVE and the reader subtracts one second when the total is
      //     negative with nonzero nanos (cuDF's ORC decoder matches).
      // The two are distinguishable per value: negative total seconds with
      // POSITIVE nanos can only come from a java-convention writer, so
      // that exact case gets the -1s adjustment and everything else is
      // signed addition. Wall-clock conversion needs a tz database, so
      // non-UTC writers fail loudly rather than shift silently; secondary
      // = nanos with the removed-trailing-zero count in the low 3 bits
      // (z > 0 means value * 10^(z+1)). Result: int64 unix-epoch
      // microseconds.
      // non-UTC writer zones no longer fail here: the decode emits
      // WALL-CLOCK micros and read_file records the zone; the Python
      // layer owns the tz database (zoneinfo via pyarrow) and converts
      // wall -> UTC there.
      constexpr int64_t kOrcEpochSeconds = 1420070400;
      auto secs = decode_int_stream(s.data, n_present, true, v2);
      auto nenc = decode_int_stream(s.secondary, n_present, false, v2);
      std::vector<int64_t> vals;
      vals.reserve(n_present);
      for (int64_t k = 0; k < n_present; ++k) {
        int64_t v = nenc[k];
        int64_t nanos = v >> 3;
        int z = static_cast<int>(v & 7);
        if (z != 0) {
          for (int q = 0; q < z + 1; ++q) nanos *= 10;
        }
        int64_t total_secs = secs[k] + kOrcEpochSeconds;
        if (total_secs < 0 && nanos > 0) total_secs -= 1;
        vals.push_back(total_secs * 1000000 + nanos / 1000);
      }
      scatter_i64(vals);
      break;
    }
    case Kind::BINARY:
    case Kind::STRING:
    case Kind::VARCHAR:
    case Kind::CHAR: {
      if (out.offsets.empty()) out.offsets.push_back(0);
      if (dict_enc) {
        auto lens = decode_int_stream(s.length, s.dict_size, false, v2);
        std::vector<std::pair<uint64_t, uint64_t>> entries;  // (start, len)
        uint64_t at = 0;
        for (int64_t l : lens) {
          entries.emplace_back(at, l);
          at += l;
        }
        if (at > s.dict.size()) fail("dictionary chars underrun");
        auto idx = decode_int_stream(s.data, n_present, false, v2);
        int64_t next = 0;
        for (int64_t r = 0; r < stripe_rows; ++r) {
          int32_t last = out.offsets.back();
          if (valid[r]) {
            uint64_t id = static_cast<uint64_t>(idx[next++]);
            if (id >= entries.size()) fail("dictionary index oob");
            auto [st, ln] = entries[id];
            out.chars.insert(out.chars.end(), s.dict.data() + st,
                             s.dict.data() + st + ln);
            out.offsets.push_back(last + static_cast<int32_t>(ln));
          } else {
            out.offsets.push_back(last);
          }
        }
      } else {
        auto lens = decode_int_stream(s.length, n_present, false, v2);
        uint64_t at = 0;
        int64_t next = 0;
        for (int64_t r = 0; r < stripe_rows; ++r) {
          int32_t last = out.offsets.back();
          if (valid[r]) {
            uint64_t ln = static_cast<uint64_t>(lens[next++]);
            if (at + ln > s.data.size()) fail("string chars underrun");
            out.chars.insert(out.chars.end(), s.data.data() + at,
                             s.data.data() + at + ln);
            at += ln;
            out.offsets.push_back(last + static_cast<int32_t>(ln));
          } else {
            out.offsets.push_back(last);
          }
        }
      }
      break;
    }
    default:
      fail("unsupported ORC type kind " + std::to_string(ty.kind));
  }

  if (s.has_present || !out.validity.empty()) {
    if (out.validity.size() < static_cast<size_t>(out.num_rows)) {
      out.validity.resize(out.num_rows, 1);
    }
    out.validity.insert(out.validity.end(), valid.begin(), valid.end());
  }
  out.num_rows += stripe_rows;
}

}  // namespace

std::vector<StripeInfo> stripe_infos(uint8_t const* file, uint64_t len) {
  FileMeta meta = parse_meta(file, len);
  std::vector<StripeInfo> out;
  for (auto const& st : meta.stripes) {
    StripeInfo info;
    info.num_rows = static_cast<int64_t>(st.u64(kSiNumRows));
    info.data_bytes = static_cast<int64_t>(
        st.u64(kSiIndexLength) + st.u64(kSiDataLength) +
        st.u64(kSiFooterLength));
    out.push_back(info);
  }
  return out;
}

OrcResult read_file(uint8_t const* file, uint64_t len,
                    std::optional<std::vector<int32_t>> const& columns,
                    std::optional<std::vector<int32_t>> const& stripes) {
  FileMeta meta = parse_meta(file, len);
  std::vector<int32_t> cols;
  if (columns.has_value()) {
    cols = *columns;
  } else {
    for (uint64_t i = 0; i < meta.leaves.size(); ++i) {
      cols.push_back(static_cast<int32_t>(i));
    }
  }
  std::vector<int32_t> strps;
  if (stripes.has_value()) {
    strps = *stripes;
  } else {
    for (uint64_t i = 0; i < meta.stripes.size(); ++i) {
      strps.push_back(static_cast<int32_t>(i));
    }
  }

  OrcResult res;
  bool first_stripe = true;
  for (int32_t cidx : cols) {
    if (cidx < 0 || static_cast<uint64_t>(cidx) >= meta.leaves.size()) {
      fail("column index out of range");
    }
    OrcColumn col;
    auto const& ty = meta.leaves[cidx];
    col.name = ty.name;
    col.kind = ty.kind;
    col.precision = ty.precision;
    col.scale = ty.scale;
    res.columns.push_back(std::move(col));
  }

  for (int32_t sidx : strps) {
    if (sidx < 0 || static_cast<uint64_t>(sidx) >= meta.stripes.size()) {
      fail("stripe index out of range");
    }
    auto const& stripe = meta.stripes[sidx];
    int64_t stripe_rows = static_cast<int64_t>(stripe.u64(kSiNumRows));
    // stripe footer sits after index + data; every addend is an
    // attacker-controlled varint, so check without unsigned wraparound
    uint64_t off = stripe.u64(kSiOffset);
    uint64_t ilen = stripe.u64(kSiIndexLength);
    uint64_t dlen = stripe.u64(kSiDataLength);
    uint64_t sf_len = stripe.u64(kSiFooterLength);
    if (off > len || ilen > len - off || dlen > len - off - ilen ||
        sf_len > len - off - ilen - dlen) {
      fail("stripe footer out of bounds");
    }
    uint64_t sf_off = off + ilen + dlen;
    auto sf_bytes = decode_stream(file + sf_off, sf_len, meta.compression);
    Message sf = Message::parse(sf_bytes.data(), sf_bytes.size());
    StripeDirectory dir = parse_directory(len, stripe, sf);
    if (first_stripe) {
      res.writer_timezone = dir.writer_timezone;
      first_stripe = false;
    } else if (res.writer_timezone != dir.writer_timezone) {
      // includes empty-vs-named mixes: an unrecorded zone reads as UTC
      // here, so silently adopting a sibling stripe's named zone would
      // shift that stripe's values — fail loudly instead
      fail("stripes disagree on writerTimezone ('" +
           res.writer_timezone + "' vs '" + dir.writer_timezone + "')");
    }
    for (uint64_t k = 0; k < cols.size(); ++k) {
      decode_stripe_column(file, meta, dir, cols[k], stripe_rows,
                           res.columns[k]);
    }
    res.num_rows += stripe_rows;
  }

  // normalize all-valid masks to empty
  for (auto& col : res.columns) {
    bool all = true;
    for (uint8_t v : col.validity) {
      if (!v) { all = false; break; }
    }
    if (all) col.validity.clear();
    if ((col.kind == static_cast<int32_t>(Kind::STRING) ||
         col.kind == static_cast<int32_t>(Kind::VARCHAR) ||
         col.kind == static_cast<int32_t>(Kind::CHAR)) &&
        col.offsets.empty()) {
      col.offsets.push_back(0);
    }
  }
  return res;
}

}  // namespace orc
}  // namespace tpudf
